"""Attention ops — XLA-native path (reference:
modules/attention/attention_base.py ``NeuronAttentionBase``).

The reference dispatches between NKI flash kernels and a native compiler path
(FlashAttentionStrategy.NONE, attention_base.py:985-1034). Here the roles are
mirrored: this module is the always-available XLA path (XLA already tiles these
einsums onto the MXU and fuses the softmax); a Pallas flash kernel
(``ops/flash_attention.py``, added separately) is the fast path for
long-context prefill.

Layout conventions (TPU-friendly: head_dim last = 128-lane dim):
  q:        (B, T, Hq, D)
  k/v:      (B, S, Hkv, D)
  mask:     (B, T, S) boolean, True = attend
All softmax math in fp32 (matches reference numerics: manual_softmax in
modules/attention/utils.py computes in fp32).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

NEG_INF = -30000.0  # large-negative fill used instead of -inf (reference uses
                    # torch.finfo.min clamps; finite value avoids fp16/bf16 NaNs)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D)
    (reference: modules/attention/utils.py ``repeat_kv``)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def alibi_slopes(num_heads: int, variant: str = "bloom") -> "np.ndarray":
    """Per-head ALiBi slopes (paper 2108.12409). "bloom" reproduces HF
    build_alibi_tensor (closest power of two + interleaved extras);
    "mpt" reproduces build_mpt_alibi_tensor (ceil power of two with
    alibi_bias_max=8, odd slopes first). Identical for power-of-two head
    counts."""
    import math

    import numpy as np
    if variant == "bloom":
        cp2 = 2 ** math.floor(math.log2(num_heads))
        base = 2.0 ** (-(2.0 ** -(math.log2(cp2) - 3)))
        slopes = base ** np.arange(1, cp2 + 1, dtype=np.float64)
        if cp2 != num_heads:
            extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * cp2) - 3)))
            n_extra = min(2 * cp2, num_heads) - cp2
            extra = extra_base ** np.arange(1, 2 * n_extra, 2,
                                            dtype=np.float64)
            slopes = np.concatenate([slopes, extra])
        return slopes.astype(np.float32)
    if variant == "mpt":
        n2 = 2 ** math.ceil(math.log2(num_heads))
        base = np.arange(1, n2 + 1, dtype=np.float64) * (8.0 / n2)
        slopes = 1.0 / np.power(2.0, base)
        if n2 != num_heads:
            slopes = np.concatenate([slopes[1::2], slopes[0::2]])[:num_heads]
        return slopes.astype(np.float32)
    raise ValueError(f"unknown alibi variant {variant!r}")


def _alibi_bias(alibi, hkv: int, g: int):
    """(slopes (Hq,), kv_pos (B,S) or (1,S)) -> additive score bias
    (B, Hkv, G, 1, S) in fp32."""
    slopes, kv_pos = alibi
    sl = slopes.astype(jnp.float32).reshape(1, hkv, g, 1, 1)
    return sl * kv_pos.astype(jnp.float32)[:, None, None, None, :]


def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
        mask: Optional[jnp.ndarray], scale: float,
        logits_soft_cap: Optional[float] = None,
        sink: Optional[jnp.ndarray] = None,
        alibi=None) -> jnp.ndarray:
    """Masked multi-head attention core with GQA grouping.

    q (B,T,Hq,D), k/v (B,S,Hkv,D); Hq % Hkv == 0. Returns (B,T,Hq,D).
    ``sink``: per-head learned softmax sink logits (B-broadcast), shape (Hq,)
    (reference: modules/attention/sink.py — gpt-oss learned sinks).
    """
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    # QK^T on the MXU in the storage dtype (bf16 x bf16 -> fp32 accumulate);
    # softmax math stays fp32. This avoids materializing an fp32 copy of the
    # whole KV cache every decode step (the decode path is HBM-bound).
    qk = q.reshape(b, t, hkv, g, d)
    # scores: (B, Hkv, G, T, S)
    scores = jnp.einsum("bthgd,bshd->bhgts", qk, k,
                        preferred_element_type=jnp.float32) * scale
    if alibi is not None:
        scores = scores + _alibi_bias(alibi, hkv, g)
    if logits_soft_cap is not None:
        scores = logits_soft_cap * jnp.tanh(scores / logits_soft_cap)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    if sink is not None:
        # append a virtual sink column to the softmax denominator
        sink_col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1),
            (b, hkv, g, t, 1))
        scores_all = jnp.concatenate([scores, sink_col], axis=-1)
        m = jnp.max(scores_all, axis=-1, keepdims=True)
        e = jnp.exp(scores_all - m)
        probs = (e / jnp.sum(e, axis=-1, keepdims=True))[..., :-1]
    else:
        m = jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bhgts,bshd->bthgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    # v head dim may differ from q/k head dim (MLA, deepseek)
    return out.reshape(b, t, hq, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mask construction (reference: models/model_base.py:197-376 — causal /
# windowed / chunked / speculation masks built on device from position ids)
# ---------------------------------------------------------------------------

def mha_hl(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: Optional[jnp.ndarray], scale: float,
           logits_soft_cap: Optional[float] = None,
           sink: Optional[jnp.ndarray] = None,
           alibi=None) -> jnp.ndarray:
    """:func:`mha` over the native KV-cache storage layouts
    (modules/kv_cache.py): k TRANSPOSED (B, Hkv, D, S), v (B, Hkv, S, D).
    Each einsum contracts its cache operand in place — with a shared
    layout, one of the two dots costs a materialized relayout of the live
    cache per layer per decode step (the score dot wants S on lanes, the
    value dot wants D on lanes)."""
    b, t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qk = q.reshape(b, t, hkv, g, d)
    scores = jnp.einsum("bthgd,bhds->bhgts", qk, k,
                        preferred_element_type=jnp.float32) * scale
    if alibi is not None:
        scores = scores + _alibi_bias(alibi, hkv, g)
    if logits_soft_cap is not None:
        scores = logits_soft_cap * jnp.tanh(scores / logits_soft_cap)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    if sink is not None:
        sink_col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1),
            (b, hkv, g, t, 1))
        scores_all = jnp.concatenate([scores, sink_col], axis=-1)
        m = jnp.max(scores_all, axis=-1, keepdims=True)
        e = jnp.exp(scores_all - m)
        probs = (e / jnp.sum(e, axis=-1, keepdims=True))[..., :-1]
    else:
        m = jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bhgts,bhsd->bthgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, hq, v.shape[-1]).astype(q.dtype)


def causal_mask(position_ids: jnp.ndarray, kv_positions: jnp.ndarray,
                kv_valid: Optional[jnp.ndarray] = None,
                window: int = 0, chunk: int = 0) -> jnp.ndarray:
    """Boolean attend-mask (B, T, S) from query positions (B, T) and key
    positions (B, S).

    window > 0: sliding-window attention (attend iff 0 <= qpos-kpos < window).
    chunk  > 0: chunked/local attention (attend iff same chunk, Llama4-style).
    kv_valid: (B, S) bool — which cache slots hold real tokens.
    """
    qp = position_ids[:, :, None]
    kp = kv_positions[:, None, :]
    m = kp <= qp
    if window > 0:
        m &= (qp - kp) < window
    if chunk > 0:
        m &= (qp // chunk) == (kp // chunk)
    if kv_valid is not None:
        m &= kv_valid[:, None, :]
    return m


def prefill_causal_mask(seq_len: int, position_ids: jnp.ndarray,
                        window: int = 0, chunk: int = 0) -> jnp.ndarray:
    """Standard in-context causal mask for context encoding: query/key
    positions are both ``position_ids`` (B, S) over the padded window."""
    return causal_mask(position_ids, position_ids, None, window, chunk)


def rolling_decode_mask(position_ids: jnp.ndarray, window: int
                        ) -> jnp.ndarray:
    """Decode mask over a ROLLING cache of ``window`` slots where slot j
    holds position p_j = P - ((P - j) mod w) for current position P —
    attend iff that position exists (p_j >= 0); the window constraint
    p_j > P - w is inherent to the layout (reference rolling write:
    kv_cache_manager.py:605-606)."""
    qp = position_ids[:, :, None]                    # (B, T, 1)
    j = jnp.arange(window, dtype=position_ids.dtype)[None, None, :]
    pj = qp - ((qp - j) % window)
    return pj >= 0


def decode_mask(position_ids: jnp.ndarray, cache_len: int,
                window: int = 0, chunk: int = 0) -> jnp.ndarray:
    """Mask for token generation over a contiguous cache of length
    ``cache_len`` whose slot i holds position i. position_ids: (B, T)."""
    kv_pos = jnp.arange(cache_len, dtype=position_ids.dtype)[None, :]
    kv_pos = jnp.broadcast_to(kv_pos, (position_ids.shape[0], cache_len))
    return causal_mask(position_ids, kv_pos, None, window, chunk)


def speculation_mask(position_ids: jnp.ndarray, cache_len: int,
                     window: int = 0) -> jnp.ndarray:
    """Mask for a block of k speculative tokens (B, k) against the cache —
    same math as decode_mask; kept as a named entry point for parity with the
    reference's speculation mask branch (model_base.py:259-306)."""
    return decode_mask(position_ids, cache_len, window)
