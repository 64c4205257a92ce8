"""KV cache management (reference: modules/kvcache/kv_cache_manager.py).

TPU-native design: the cache is a pytree of two stacked arrays
  k, v : (num_layers, batch, num_kv_heads, max_seq, head_dim)
sharded P(None, "dp", "tp", None, None) and **donated** into every jitted
step — ``jax.jit(..., donate_argnums)`` is the direct analog of the
reference's input/output aliasing (reference: models/model_wrapper.py:1578-1627,
noted in SURVEY §1).

Layout rationale: HEAD-LEADING — (seq, head_dim) are the minor (tiled) dims:
head_dim on the 128-lane axis, seq on the sublane axis, heads a leading dim.
This is the layout Pallas kernels want (ops/decode_attention.py streams
per-head (block_s, head_dim) blocks with legal BlockSpecs and no in-kernel
relayout; a head-minor layout would make every per-head slice a cross-tile
sublane gather). The reference's 128-tiling of S for cascaded reductions
(kv_cache_manager.py:29-80) is unnecessary here; XLA handles reduction
tiling, and :func:`read_layer` hands the XLA path a (B, S, H, D) view whose
transpose fuses into the attention einsum.

Supported behaviors mirrored from the reference:
  * CTE write  = batch-row scatter at seq_ids (continuous batching single-seq
    update, kv_cache_manager.py:483-497)
  * TKG write  = scatter at (seq_ids, position_ids) (:431-586)
  * sliding-window rolling write pos % window (:605-606) — wired through
    the model base for uniform-window models (spec.rolling_window): the
    cache holds w slots, decode uses attention.rolling_decode_mask (the
    position-mapping mask), prefill writes only each row's last w positions
  * per-layer cache sizes for mixed local/global attention (gpt-oss manager)
  * fp8 KV quantization, direct-cast mode (:636-692)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_MP


@dataclass(frozen=True)
class KVCacheSpec:
    num_layers: int
    batch_size: int
    max_seq_len: int
    num_kv_heads: int     # padded/replicated per GQASharding
    head_dim: int         # K head dim (MLA: qk_nope + qk_rope, expanded)
    dtype: jnp.dtype = jnp.bfloat16
    window: int = 0       # >0: rolling sliding-window cache of this length
    # MLA: v dim != k dim. THIS (contiguous) cache holds an MLA model's
    # EXPANDED heads; the paged pool holds its latent rows instead
    # (modules/block_kv_cache.latent_page: 576 values a token a layer
    # against heads x (192 + 128))
    v_head_dim: Optional[int] = None

    @property
    def cache_len(self) -> int:
        return min(self.max_seq_len, self.window) if self.window > 0 else self.max_seq_len

    @property
    def v_dim(self) -> int:
        return self.v_head_dim if self.v_head_dim is not None else self.head_dim

    @property
    def k_shape(self) -> Tuple[int, ...]:
        # K stored TRANSPOSED (L, B, H, D, S): the decode score matmul
        # contracts D with S free, so S lands on the lane axis naturally;
        # V keeps (L, B, H, S, D) for the value matmul (contract S, D on
        # lanes). One layout per consumer = no per-layer relayout copies
        # (the reference ships the same transposed-K option,
        # models/config.py:395-415 "KV tiling + transposed-K").
        return (self.num_layers, self.batch_size, self.num_kv_heads,
                self.head_dim, self.cache_len)

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.k_shape

    @property
    def v_shape(self) -> Tuple[int, ...]:
        return (self.num_layers, self.batch_size, self.num_kv_heads,
                self.cache_len, self.v_dim)


def cache_len_of(cache) -> int:
    """Cache sequence capacity from the stacked cache pytree (V layout
    (L, B, H, S, D))."""
    return cache["v"].shape[3]


def k_pspec(flash_decoding: bool = False) -> P:
    """Transposed-K layout (L, B, H, D, S). Flash decoding shards S over
    the "cp" axis — the decode-time sequence sharding of the reference
    (modules/flashdecode/utils.py)."""
    from ..parallel.mesh import AXIS_CP
    return P(None, AXIS_DP, AXIS_MP, None, AXIS_CP if flash_decoding else None)


def v_pspec(flash_decoding: bool = False) -> P:
    from ..parallel.mesh import AXIS_CP
    return P(None, AXIS_DP, AXIS_MP, AXIS_CP if flash_decoding else None, None)


def cache_pspec(flash_decoding: bool = False):
    """Per-leaf cache PartitionSpecs keyed like the cache pytree."""
    return {"k": k_pspec(flash_decoding), "v": v_pspec(flash_decoding)}


def init_cache(spec: KVCacheSpec, mesh: Optional[Mesh] = None,
               flash_decoding: bool = False):
    """Zero-initialized {'k','v'} cache, device-placed with the cache sharding."""
    def zeros(shape, pspec):
        return jnp.zeros(shape, spec.dtype,
                         device=(NamedSharding(mesh, pspec)
                                 if mesh is not None else None))

    return {"k": zeros(spec.k_shape, k_pspec(flash_decoding)),
            "v": zeros(spec.v_shape, v_pspec(flash_decoding))}


def init_mixed_cache(spec: KVCacheSpec, layer_pattern, window: int,
                     mesh: Optional[Mesh] = None):
    """MIXED per-layer cache sizes (reference: gpt-oss per-layer KV,
    modules/kvcache/gpt_oss_kv_cache_manager.py): global layers get
    full-seq rows in {"k","v"}; LOCAL layers (layer_pattern[i] True) get
    ROLLING window-sized rows in {"k_l","v_l"} — decode KV bytes scale
    with W on local layers instead of seq_len."""
    import dataclasses
    n_local = sum(bool(x) for x in layer_pattern)
    n_global = spec.num_layers - n_local
    g_spec = dataclasses.replace(spec, num_layers=max(n_global, 1))
    l_spec = dataclasses.replace(spec, num_layers=max(n_local, 1),
                                 window=window)
    cache = init_cache(g_spec, mesh)
    local = init_cache(l_spec, mesh)
    cache["k_l"] = local["k"]
    cache["v_l"] = local["v"]
    return cache


def mixed_layer_map(layer_pattern):
    """Absolute layer index -> index within its own (local/global) stack."""
    idx = []
    n_l = n_g = 0
    for is_local in layer_pattern:
        if is_local:
            idx.append(n_l)
            n_l += 1
        else:
            idx.append(n_g)
            n_g += 1
    return idx


def fold_rolling_prefill(scratch: jnp.ndarray, seq_lens: jnp.ndarray,
                         window: int, k_transposed: bool = False
                         ) -> jnp.ndarray:
    """Convert a full-length prefill scratch cache (L', B, H, D, S)/(...,
    S, D) into the rolling layout (W slots, slot j holds the LATEST
    position p <= seq_len-1 with p % W == j; unwritten slots zero) —
    the mixed-cache prefill epilogue (reference: gpt-oss manager CTE
    write path)."""
    s_axis = 4 if k_transposed else 3
    last = seq_lens.astype(jnp.int32) - 1                       # (B,)
    j = jnp.arange(window, dtype=jnp.int32)                     # (W,)
    q = last[:, None] - ((last[:, None] - j[None, :]) % window)  # (B, W)
    valid = q >= 0
    qc = jnp.clip(q, 0, scratch.shape[s_axis] - 1)
    if k_transposed:
        idx = qc[None, :, None, None, :]                        # (1,B,1,1,W)
        gathered = jnp.take_along_axis(
            scratch, jnp.broadcast_to(
                idx, scratch.shape[:4] + (window,)), axis=4)
        return jnp.where(valid[None, :, None, None, :], gathered, 0)
    idx = qc[None, :, None, :, None]                            # (1,B,1,W,1)
    gathered = jnp.take_along_axis(
        scratch, jnp.broadcast_to(
            idx, scratch.shape[:3] + (window, scratch.shape[4])), axis=3)
    return jnp.where(valid[None, :, None, :, None], gathered, 0)


def quantize_kv(x: jnp.ndarray, dtype, scale: Optional[float] = None) -> jnp.ndarray:
    """KV quantization on write (reference: kv_cache_manager.py:636-692):
    direct-cast mode (scale=None) or scaled mode — store x/scale so the fp8
    dynamic range covers the KV distribution."""
    if scale is not None and scale != 1.0:
        x = x.astype(jnp.float32) / scale
    return x.astype(dtype)


def dequantize_kv(x: jnp.ndarray, dtype, scale: Optional[float] = None) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv` on read."""
    if scale is not None and scale != 1.0:
        return (x.astype(jnp.float32) * scale).astype(dtype)
    return x.astype(dtype)


def write_prefill(cache_layer: jnp.ndarray, new: jnp.ndarray,
                  seq_ids: jnp.ndarray, start: jnp.ndarray | int = 0) -> jnp.ndarray:
    """Write a full prefill window into cache rows ``seq_ids``.

    cache_layer (B, H, S, D) head-leading (one V layer; use
    ``k_transposed`` paths for K); new (b, s, H, D); seq_ids (b,). start:
    slot offset (chunked/windowed prefill writes at a running offset,
    reference: fill_prefix / dynamic_update_slice in kvcache/utils.py).
    """
    s = new.shape[1]
    pos = (jnp.arange(s, dtype=jnp.int32) + start)[None, :]        # (1, s)
    pos = jnp.broadcast_to(pos, (new.shape[0], s))
    return write_tokens(cache_layer, new, seq_ids, pos)


def write_tokens(cache_layer: jnp.ndarray, new: jnp.ndarray,
                 seq_ids: jnp.ndarray, positions: jnp.ndarray,
                 window: int = 0) -> jnp.ndarray:
    """Scatter active tokens into the cache (TKG write,
    reference: kv_cache_manager.py:431-586).

    cache_layer (B, H, S, D) head-leading (one V layer); new (b, t, H, D);
    seq_ids (b,); positions (b, t).
    window > 0 applies the rolling write positions % window
    (reference: :605-606 uses % (w-1) to keep one slot for the active token;
    here the active token lives in the same cache so plain modulo is correct).
    """
    return write_tokens_at_layer(cache_layer[None], new, 0, seq_ids,
                                 positions, window)[0]


def write_tokens_at_layer(cache: jnp.ndarray, new: jnp.ndarray, layer,
                          seq_ids: jnp.ndarray, positions: jnp.ndarray,
                          window: int = 0,
                          k_transposed: bool = False) -> jnp.ndarray:
    """In-place token write into the FULL stacked cache at ``layer`` (a
    traced scalar inside the layer scan). ``new`` stays in the projection
    layout (b, t, H, D); ``k_transposed`` writes into the transposed-K
    layout (L, B, H, D, S) instead of the V layout (L, B, H, S, D).
    Writing into the scan-carried full buffer — instead of rewriting a
    per-layer slice into stacked scan outputs — keeps the decode-step HBM
    traffic at read-cache + write-tokens rather than read-cache +
    write-cache (the donated carry makes the update in-place)."""
    if window > 0:
        positions = positions % window
    b, t, h, d = new.shape
    new = jnp.swapaxes(new.astype(cache.dtype), 1, 2)       # (b, H, t, D)
    li = jnp.asarray(layer, jnp.int32)
    s_max = cache.shape[4] if k_transposed else cache.shape[3]
    zero = jnp.zeros((), jnp.int32)
    if t == 1 and b <= 16:
        # decode hot path: per-row dynamic-update-slice instead of one
        # advanced-index scatter — the scatter op forces a layout on the
        # loop-carried cache that conflicts with the attention einsums,
        # costing a materialized relayout of the live cache per layer per
        # step (measured 0.31 -> 0.15 ms/step on v5e at B=2/S=1024).
        # Out-of-range drop semantics are kept by writing back the old
        # value (the tiny read-modify-write is free next to the DUS).
        for i in range(b):
            pos_i = positions[i, 0]
            pos_c = jnp.clip(pos_i, 0, s_max - 1)
            row = seq_ids[i].astype(jnp.int32)
            if k_transposed:
                start = (li, row, zero, zero, pos_c)
                upd = new[i].reshape(h, d)[None, None, :, :, None]
            else:
                start = (li, row, zero, pos_c, zero)
                upd = new[i][None, None, :, :, :]           # (1, 1, H, 1, D)
            old = jax.lax.dynamic_slice(cache, start, upd.shape)
            valid = jnp.logical_and(pos_i >= 0, pos_i < s_max)
            cache = jax.lax.dynamic_update_slice(
                cache, jnp.where(valid, upd, old), start)
        return cache
    hidx = jnp.arange(h, dtype=jnp.int32)
    if k_transposed:
        # advanced indices (b, H, t) around the sliced D dim: the advanced
        # block moves to the front, so the update is (b, H, t, D)
        return cache.at[li, seq_ids[:, None, None], hidx[None, :, None], :,
                        positions[:, None, :]].set(
            new, mode="drop", unique_indices=False)
    return cache.at[li, seq_ids[:, None, None], hidx[None, :, None],
                    positions[:, None, :]].set(
        new, mode="drop", unique_indices=False)


def write_prefill_at_layer(cache: jnp.ndarray, new: jnp.ndarray, layer,
                           seq_ids: jnp.ndarray,
                           start: jnp.ndarray | int = 0,
                           identity_seq_ids: bool = False,
                           k_transposed: bool = False) -> jnp.ndarray:
    """Stacked-cache prefill write: the window goes to slots [start,
    start+s) of rows ``seq_ids`` (start > 0 = chunked/windowed prefill at a
    running offset). identity_seq_ids=True (static guarantee that seq_ids
    == arange over the full cache batch) takes the dynamic-update-slice
    fast path — one contiguous block write instead of a b*H*s-row scatter."""
    b, s, h, _ = new.shape
    li = jnp.asarray(layer, jnp.int32)
    if identity_seq_ids and b == cache.shape[1]:
        if k_transposed:
            new_t = jnp.transpose(new.astype(cache.dtype), (0, 2, 3, 1))
            return jax.lax.dynamic_update_slice(
                cache, new_t[None],
                (li, 0, 0, 0, jnp.asarray(start, jnp.int32)))
        new_t = jnp.swapaxes(new.astype(cache.dtype), 1, 2)   # (b, H, s, D)
        return jax.lax.dynamic_update_slice(
            cache, new_t[None],
            (li, 0, 0, jnp.asarray(start, jnp.int32), 0))
    pos = (jnp.arange(s, dtype=jnp.int32) + start)[None, :]
    pos = jnp.broadcast_to(pos, (b, s))
    return write_tokens_at_layer(cache, new, layer, seq_ids, pos,
                                 k_transposed=k_transposed)


def read_layer_hl(cache: jnp.ndarray, layer) -> jnp.ndarray:
    """Dynamic-slice one layer out of the stacked (L, B, H, S, D) cache in
    its native head-leading layout — pair with ``attention.mha_hl`` so the
    cache is contracted in place (no transposed copy)."""
    return jax.lax.dynamic_index_in_dim(
        cache, jnp.asarray(layer, jnp.int32), 0, keepdims=False)


def read_layer(cache: jnp.ndarray, layer) -> jnp.ndarray:
    """Dynamic-slice one layer out of the stacked (L, B, H, S, D) cache and
    hand it back as (B, S, H, D) — the projection-layout view. NOTE: XLA
    materializes the swapaxes as a transposed copy of the layer when the
    consumer is an einsum over a scatter-updated buffer — the decode hot
    path uses :func:`read_layer_hl` + ``mha_hl`` instead."""
    return jnp.swapaxes(read_layer_hl(cache, layer), 1, 2)


def gather_cache_rows(cache_layer: jnp.ndarray, seq_ids: jnp.ndarray) -> jnp.ndarray:
    """Select the batch rows for the running requests (continuous batching:
    compiled batch may be a subset/permutation of cache lines)."""
    return cache_layer[seq_ids]


class KVCacheManager:
    """Thin stateful wrapper holding the spec + cache pytree.

    The traced model functions use the pure functions above; this class is the
    host-side owner used by the application layer (mirrors the role of
    reference KVCacheManager without being traced itself).
    """

    def __init__(self, spec: KVCacheSpec, mesh: Optional[Mesh] = None):
        self.spec = spec
        self.mesh = mesh
        self.cache = init_cache(spec, mesh)

    def reset(self):
        self.cache = jax.tree.map(lambda x: jnp.zeros_like(x), self.cache)

    @property
    def nbytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.cache))
