"""The plain reference: one float32 forward pass in straightforward
``jax.numpy``, written from the published model descriptions (the OLMoE paper
and ``modeling_olmoe.py`` / ``modeling_mistral.py`` of transformers as
documented), not from this repository's ``model_base.py``. No kernels, no
cache, no batching tricks: the whole sequence goes through every layer with a
causal mask.

Weights are a mapping from the published parameter names to arrays in the
published orientation (``Linear.weight`` is ``(out, in)``), with two
departures, both layout only: per-layer tensors are stacked on a leading
layer axis under the name with ``{i}`` left in (``model.layers.{i}.…``), and
the experts of a layer are stacked on a second axis under
``model.layers.{i}.mlp.experts.{e}.<proj>.weight``. ``weights.HfView`` turns
the same arrays back into the flat published names for the program's loader.

The caller sets ``jax.default_matmul_precision("highest")`` as a context
manager around the call (never the global flag: under it Mosaic refuses the
served model's bf16 ``ragged_dot``, PERF.md §7).

What each model type adds to the shared decoder (pre-norm residual blocks,
RMSNorm in float32, rotary embedding in the half-rotation form, grouped-query
softmax attention scaled by ``head_dim ** -0.5``, SwiGLU):

* ``olmoe``: RMSNorm over the FULL projected width of q and of k (all heads
  together) before the split into heads; a router that takes the softmax over
  all experts, keeps the top ``num_experts_per_tok`` probabilities and, with
  ``norm_topk_prob`` false, does NOT renormalise them.
* ``mistral``: nothing; ``head_dim`` is read from the config when present
  (Mistral-Nemo's 128 is not ``hidden_size / num_attention_heads``). A
  ``sliding_window`` other than null is refused: no cell uses one.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

L = "model.layers.{i}."
EXPERT = L + "mlp.experts.{e}."


def _rms_norm(x, weight, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: (B, S, heads, D). Half-rotation rotary embedding."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)            # (S, D)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def _linear(x, weight):
    """``x @ weight.T`` with ``weight`` in the published (out, in) form."""
    return jnp.einsum("...i,oi->...o", x, weight.astype(jnp.float32))


def _attention(cfg, w, i, h):
    b, s, _ = h.shape
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    p = L + "self_attn."
    q = _linear(h, w[p + "q_proj.weight"][i])
    k = _linear(h, w[p + "k_proj.weight"][i])
    v = _linear(h, w[p + "v_proj.weight"][i])
    if cfg["model_type"] == "olmoe":
        q = _rms_norm(q, w[p + "q_norm.weight"][i], cfg["rms_norm_eps"])
        k = _rms_norm(k, w[p + "k_norm.weight"][i], cfg["rms_norm_eps"])
    q = q.reshape(b, s, nq, d)
    k = k.reshape(b, s, nkv, d)
    v = v.reshape(b, s, nkv, d)
    pos = jnp.arange(s)
    q = _rope(q, pos, float(cfg["rope_theta"]))
    k = _rope(k, pos, float(cfg["rope_theta"]))
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nq * d)
    return _linear(out, w[p + "o_proj.weight"][i])


def _swiglu(x, gate, up, down):
    return _linear(jax.nn.silu(_linear(x, gate)) * _linear(x, up), down)


def _moe(cfg, w, i, h):
    b, s, hid = h.shape
    x = h.reshape(b * s, hid)
    probs = jax.nn.softmax(_linear(x, w[L + "mlp.gate.weight"][i]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", False):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_exp = cfg["num_experts"]
    # how clearly the routing is decided: the relative gap between the last
    # probability picked and the first one left out (before renormalising)
    k = cfg["num_experts_per_tok"]
    edge = jax.lax.top_k(probs, k + 1)[0]
    margin = ((edge[:, k - 1] - edge[:, k]) / edge[:, k - 1]).reshape(b, s)
    weight_of = jnp.sum(jax.nn.one_hot(top_e, n_exp) * top_p[..., None],
                        axis=1)                            # (N, E), 0 if unpicked
    gate = w[EXPERT + "gate_proj.weight"][i].astype(jnp.float32)   # (E, I, H)
    up = w[EXPERT + "up_proj.weight"][i].astype(jnp.float32)
    down = w[EXPERT + "down_proj.weight"][i].astype(jnp.float32)   # (E, H, I)
    act = jax.nn.silu(jnp.einsum("nh,eih->nei", x, gate)) \
        * jnp.einsum("nh,eih->nei", x, up)
    every = jnp.einsum("nei,ehi->neh", act, down)          # every expert's output
    return (jnp.einsum("ne,neh->nh", weight_of, every).reshape(b, s, hid),
            margin)


def forward(cfg: Dict[str, Any], w: Mapping[str, Any], ids,
            with_margins: bool = False):
    """Next-token logits ``(B, S, vocab)`` in float32 for token ids ``(B, S)``
    under the published config ``cfg`` (a dict with the config.json keys).

    ``with_margins`` also returns, per position ``(B, S)``, the smallest
    routing margin over the expert layers (``inf`` for a dense model): where
    it is small the model's function jumps, and a bf16 evaluation may land
    on other experts than this one without being wrong."""
    if cfg["model_type"] not in ("olmoe", "mistral"):
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg.get("sliding_window"):
        raise ValueError("the reference has no sliding window")
    eps = cfg["rms_norm_eps"]
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        h = _rms_norm(x, w[L + "input_layernorm.weight"][i], eps)
        x = x + _attention(cfg, w, i, h)
        h = _rms_norm(x, w[L + "post_attention_layernorm.weight"][i], eps)
        if "num_experts" in cfg:
            out, margin = _moe(cfg, w, i, h)
            x = x + out
            margins = jnp.minimum(margins, margin)
        else:
            x = x + _swiglu(h, w[L + "mlp.gate_proj.weight"][i],
                            w[L + "mlp.up_proj.weight"][i],
                            w[L + "mlp.down_proj.weight"][i])
    x = _rms_norm(x, w["model.norm.weight"], eps)
    head = (w["model.embed_tokens.weight"] if cfg.get("tie_word_embeddings")
            else w["lm_head.weight"])
    logits = _linear(x, head)
    return (logits, margins) if with_margins else logits


# the shared pieces, under the names a ``references/<model_type>.py`` imports
# (a new architecture is a new file that reuses these, not an edit here)
rms_norm, rope, linear, swiglu = _rms_norm, _rope, _linear, _swiglu
