"""Pallas decode (token-generation) attention — TPU-native replacement for
the reference's NKI TKG attention kernels
(reference: modules/attention/attention_base.py:1186-1382 ``attention_block_tkg``
mega-kernel path and :1383-1461 decomposed prior+active attention).

Decomposition (same as the reference's decomposed TKG attention): the new
token's K/V never round-trips through the cache for the score computation —
the kernel attends over the PRIOR cache rows (0..pos_b-1) plus the ACTIVE
token handled in-registers, so the cache scatter write can be scheduled
independently by XLA.

The win over the XLA path is bandwidth: the grid walks cache blocks along S
and collapses every block past each row's live length onto the last live
block via the BlockSpec index map — Pallas elides the DMA when consecutive
grid steps map to the same block, so a 4k-slot cache at position 500 streams
~512 slots, not 4096 (the reference kernel gets the same effect from
explicit DMA skipping, kvcache/utils.py batch-write kernel).

Layouts (native cache layouts, modules/kv_cache.py): q (B, Hq, D); k cache
TRANSPOSED (L, B, Hkv, D, S), v cache (L, B, Hkv, S, D) — the minor/tiled
dims per block are (D, block_s) for K and (block_s, D) for V, so each block
is one contiguous DMA, a legal Mosaic BlockSpec, and feeds its dot in its
natural orientation (a head-minor layout would make every per-head block
shape (…,1,D), which TPU lowering rejects); new k/v (B, Hkv, D). All
softmax math fp32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38

#: largest scalar-prefetch operand (layer, window, lens, block table) the paged
#: kernel is asked to stage. v5e SMEM is 1 MiB and the kernel's own scoped
#: use takes a few KiB of it: compiling for the v5e topology, 1,040,408 B
#: went through and 1,044,744 B was refused (RESOURCE_EXHAUSTED, space=smem).
PAGED_TABLE_SMEM_BYTES = 1_040_408


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, nk_ref, nv_ref, sink_ref,
                   o_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, block_s: int, nh: int,
                   soft_cap: Optional[float], has_sink: bool,
                   kv_scale: Optional[float] = None):
    """Scalar-prefetch layout: lens_ref = [layer_idx, window, len_0, ...,
    len_{B-1}] (layer_idx consumed by the index maps of the stacked-cache
    variant; window is DYNAMIC so alternating local/global layer patterns
    can pass their per-layer window through one scan body — reference:
    gemma3 / gpt_oss alternating attention, SURVEY §2.7).

    ``nh`` kv-heads are processed per grid step (an unrolled in-kernel
    loop over leading block dims — static indexing, no relayout): the
    coarse grid keeps the per-step overhead off the critical path, which
    is what made the fine-grained one-head-per-step variant lose to XLA."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    pos = lens_ref[2 + b]                   # prior length of this row
    w = lens_ref[1]                         # sliding window (0 = unlimited)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    k_start = j * block_s
    in_window = jnp.logical_or(w == 0, k_start + block_s > pos - w)

    @pl.when(jnp.logical_and(k_start < pos, in_window))
    def _prior():
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[3], block_s), 1)
        valid = kpos < pos
        valid = jnp.logical_and(
            valid, jnp.logical_or(w == 0, pos - kpos < w))
        for hh in range(nh):
            q = q_ref[0, 0, hh].astype(jnp.float32)        # (G, D)
            k = k_ref[0, 0, hh].astype(jnp.float32)        # (D, bs) transposed
            v = v_ref[0, 0, hh].astype(jnp.float32)        # (bs, D)
            if kv_scale is not None:
                # scaled KV quantization: stored value = x / kv_scale
                # (reference: kv_cache_manager.py:636-692 scaled fp8 mode);
                # the dequant rides the fp32 cast already on the block load
                k = k * kv_scale
                v = v * kv_scale
            s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)      # (G, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[hh, :, 0:1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_ref[hh, :, 0:1] = (l_ref[hh, :, 0:1] * alpha
                                 + jnp.sum(p, -1, keepdims=True))
            acc_ref[hh] = acc_ref[hh] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[hh, :, 0:1] = m_cur

    @pl.when(j == nj - 1)
    def _active_and_finalize():
        # active token: its score joins the softmax; its V joins the acc
        for hh in range(nh):
            q = q_ref[0, 0, hh].astype(jnp.float32)        # (G, D)
            kn = nk_ref[0, 0, hh].astype(jnp.float32)      # (1, D)
            vn = nv_ref[0, 0, hh].astype(jnp.float32)      # (1, D)
            s = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)      # (G, 1)
            m_prev = m_ref[hh, :, 0:1]
            m_cur = jnp.maximum(m_prev, s)
            if has_sink:
                # learned per-head sink joins the denominator only
                # (reference: modules/attention/sink.py)
                sk = sink_ref[0, hh].astype(jnp.float32).reshape(-1)[:, None]
                m_cur = jnp.maximum(m_cur, sk)             # sk (G, 1)
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)                         # (G, 1)
            l_new = l_ref[hh, :, 0:1] * alpha + p
            if has_sink:
                l_new = l_new + jnp.exp(sk - m_cur)
            acc = acc_ref[hh] * alpha + p * vn             # (G, D)
            o_ref[0, 0, hh] = (acc / l_new).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "soft_cap", "kv_scale", "block_s",
                     "interpret"))
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, new_k: jnp.ndarray,
                     new_v: jnp.ndarray, lens: jnp.ndarray, *,
                     scale: float, window: int = 0,
                     soft_cap: Optional[float] = None,
                     sink: Optional[jnp.ndarray] = None,
                     kv_scale: Optional[float] = None,
                     block_s: int = 256, interpret: bool = False
                     ) -> jnp.ndarray:
    """One-token decode attention over prior cache + active token.

    q (B, Hq, D); k_cache (B, Hkv, D, S) TRANSPOSED / v_cache (B, Hkv, S, D)
    — slots [0, lens[b]) valid;
    new_k/new_v (B, Hkv, D) the active token's K/V (NOT yet required to be
    in the cache); lens (B,) int32 prior lengths; sink (Hq,) optional learned
    softmax sink logits. Returns (B, Hq, D).
    """
    return decode_attention_stacked(
        q, k_cache[None], v_cache[None], new_k, new_v,
        jnp.zeros((), jnp.int32), lens, scale=scale,
        window=jnp.asarray(window, jnp.int32), soft_cap=soft_cap, sink=sink,
        kv_scale=kv_scale, block_s=block_s, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "soft_cap", "kv_scale", "block_s", "interpret"))
def decode_attention_stacked(q: jnp.ndarray, k_cache: jnp.ndarray,
                             v_cache: jnp.ndarray, new_k: jnp.ndarray,
                             new_v: jnp.ndarray, layer: jnp.ndarray,
                             lens: jnp.ndarray, *,
                             scale: float,
                             window: Optional[jnp.ndarray] = None,
                             soft_cap: Optional[float] = None,
                             sink: Optional[jnp.ndarray] = None,
                             kv_scale: Optional[float] = None,
                             block_s: int = 256, interpret: bool = False
                             ) -> jnp.ndarray:
    """Decode attention reading layer ``layer`` (traced scalar — inside the
    layer scan) directly out of the FULL stacked cache (L, B, Hkv, S, D):
    no per-layer dynamic-slice materialization between the carry and the
    kernel; the index maps address the layer through scalar prefetch."""
    b, hq, d = q.shape
    hkv = k_cache.shape[2]
    s = k_cache.shape[4]          # K stored transposed (L, B, Hkv, D, S)
    g = hq // hkv
    block_s = min(block_s, s)
    nj = pl.cdiv(s, block_s)

    # kv-heads per grid step: as many as fit the VMEM budget (k+v blocks,
    # double-buffered), capped to bound the in-kernel unroll
    vmem_budget = 4 * 1024 * 1024
    max_nh = max(1, min(8, vmem_budget // (block_s * d * 2 * 2 * 2)))
    nh = 1
    for cand in range(max_nh, 0, -1):
        if hkv % cand == 0:
            nh = cand
            break
    hb = hkv // nh

    qr = q.reshape(b, hb, nh, g, d)
    sink_in = (sink.reshape(hb, nh, 1, g) if sink is not None
               else jnp.zeros((hb, nh, 1, g), jnp.float32))

    def q_map(bi, h, j, sc):
        return (bi, h, 0, 0, 0)

    def _live_block(bi, j, sc):
        # clamp to the live [window-start, prefix-end] block range:
        # consecutive identical indices -> Pallas skips the DMA
        pos_b = sc[2 + bi]
        last_live = jax.lax.max(
            jax.lax.div(jax.lax.max(pos_b - 1, 0), block_s), 0)
        w = sc[1]
        first_live = jax.lax.select(
            w > 0, jax.lax.max(jax.lax.div(jax.lax.max(pos_b - w, 0),
                                           block_s), 0), 0)
        return jax.lax.min(jax.lax.max(j, first_live), last_live)

    def k_map(bi, h, j, sc):
        # K stored transposed (L, B, Hkv, D, S)
        return (sc[0], bi, h, 0, _live_block(bi, j, sc))

    def v_map(bi, h, j, sc):
        return (sc[0], bi, h, _live_block(bi, j, sc), 0)

    def nkv_map(bi, h, j, sc):
        return (bi, h, 0, 0, 0)

    def sink_map(bi, h, j, sc):
        return (h, 0, 0, 0)

    grid = (b, hb, nj)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_s=block_s, nh=nh,
        soft_cap=soft_cap, has_sink=sink is not None, kv_scale=kv_scale)
    if window is None:
        window = jnp.zeros((), jnp.int32)
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(window, jnp.int32).reshape(1), lens.astype(jnp.int32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, nh, g, d), q_map),
                pl.BlockSpec((1, 1, nh, d, block_s), k_map),
                pl.BlockSpec((1, 1, nh, block_s, d), v_map),
                pl.BlockSpec((1, 1, nh, 1, d), nkv_map),
                pl.BlockSpec((1, 1, nh, 1, d), nkv_map),
                pl.BlockSpec((1, nh, 1, g), sink_map),
            ],
            out_specs=pl.BlockSpec((1, 1, nh, g, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((nh, g, d), jnp.float32),
                pltpu.VMEM((nh, g, 128), jnp.float32),
                pltpu.VMEM((nh, g, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hb, nh, g, d), q.dtype),
        interpret=interpret,
    )(scalars, qr, k_cache, v_cache,
      new_k.reshape(b, hb, nh, 1, d), new_v.reshape(b, hb, nh, 1, d),
      sink_in)
    return out.reshape(b, hq, d)


def dispatch(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
             new_k: jnp.ndarray, new_v: jnp.ndarray, layer: jnp.ndarray,
             lens: jnp.ndarray, *, scale: float,
             window: Optional[jnp.ndarray] = None,
             soft_cap: Optional[float] = None,
             sink: Optional[jnp.ndarray] = None,
             kv_scale: Optional[float] = None,
             block_s: int = 256, interpret: bool = False) -> jnp.ndarray:
    """Mesh-aware entry: shard_map the kernel over the ambient mesh's
    model-parallel axes (kv-heads over ("ep","tp")) and the decode batch
    axis ("dp"), matching the cache layout P(None,"dp",("ep","tp"),None,None)
    (modules/kv_cache.py cache_pspec) — the TPU analog of the reference
    running its TKG kernel per-rank under SPMD
    (attention_base.py:1186-1382). On a single-device (or axis-free) mesh
    runs the bare pallas_call. Returns None when kv heads cannot be
    sharded over a >1 model-parallel degree — the caller must use the XLA
    attention path there."""
    mesh = jax.sharding.get_abstract_mesh()
    b, hq, d = q.shape
    hkv = k_cache.shape[2]
    mp_axes = tuple(a for a in ("ep", "tp")
                    if a in mesh.axis_names and mesh.shape[a] > 1)
    mp = 1
    for a in mp_axes:
        mp *= mesh.shape[a]
    if mp > 1 and hkv % mp != 0:
        # kv heads not shardable over the model-parallel axes: a bare
        # pallas_call here would run REPLICATED under GSPMD (full cache
        # all-gathered to every device per layer per step) — signal the
        # caller to take the head-sharded XLA path instead
        return None
    dp_axes = tuple(a for a in ("dp",)
                    if a in mesh.axis_names and mesh.shape[a] > 1
                    and b % mesh.shape[a] == 0)
    if not mp_axes and not dp_axes:
        return decode_attention_stacked(
            q, k_cache, v_cache, new_k, new_v, layer, lens, scale=scale,
            window=window, soft_cap=soft_cap, sink=sink, kv_scale=kv_scale,
            block_s=block_s, interpret=interpret)

    if window is None:
        window = jnp.zeros((), jnp.int32)
    from jax.sharding import PartitionSpec as P
    dp = dp_axes if dp_axes else None
    mpx = mp_axes if mp_axes else None
    in_specs = [
        P(dp, mpx, None),                  # q
        P(None, dp, mpx, None, None),      # k_cache
        P(None, dp, mpx, None, None),      # v_cache
        P(dp, mpx, None),                  # new_k
        P(dp, mpx, None),                  # new_v
        P(),                               # layer
        P(dp),                             # lens
        P(),                               # window
    ]
    args = [q, k_cache, v_cache, new_k, new_v, layer, lens,
            jnp.asarray(window, jnp.int32)]
    if sink is not None:
        in_specs.append(P(mpx))
        args.append(sink)

    def body(q, kc, vc, nk, nv, layer, lens, window, *rest):
        return decode_attention_stacked(
            q, kc, vc, nk, nv, layer, lens, scale=scale, window=window,
            soft_cap=soft_cap, sink=rest[0] if rest else None,
            kv_scale=kv_scale, block_s=block_s, interpret=interpret)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(dp, mpx, None), check_vma=False)(*args)


def _paged_kernel(sc_ref, q_ref, k_ref, v_ref, nk_ref, nv_ref, sink_ref,
                  o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_s: int, nh: int,
                  soft_cap: Optional[float], has_sink: bool,
                  kv_scale: Optional[float] = None):
    """Ragged PAGED decode attention (reference: the DMA-skipping TKG
    attention over the block layout, attention_base.py:1186-1382 +
    block_kv_cache_manager.py:183-267). Scalar layout:
    [layer, window, len_0..len_{B-1}, table_{b=0,j=0}.., table_{B-1,mb-1}]
    — the index maps gather PHYSICAL pages through the block table, so the
    kernel streams only each row's live pages (grid steps past the live
    range collapse onto the last live page and Pallas elides the DMA); the
    XLA gather path materializes the whole table every layer every token."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    pos = sc_ref[2 + b]
    w = sc_ref[1]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    k_start = j * block_s
    in_window = jnp.logical_or(w == 0, k_start + block_s > pos - w)

    @pl.when(jnp.logical_and(k_start < pos, in_window))
    def _prior():
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[3], block_s), 1)
        valid = kpos < pos
        valid = jnp.logical_and(
            valid, jnp.logical_or(w == 0, pos - kpos < w))
        for hh in range(nh):
            q = q_ref[0, 0, hh].astype(jnp.float32)        # (G, D)
            k = k_ref[0, 0, :, hh, :].astype(jnp.float32)  # (bs, D)
            v = v_ref[0, 0, :, hh, :].astype(jnp.float32)  # (bs, D)
            if kv_scale is not None:
                # scaled KV dequant on the page load (reference:
                # kv_cache_manager.py:636-692 scaled fp8 mode)
                k = k * kv_scale
                v = v * kv_scale
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)      # (G, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[hh, :, 0:1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_ref[hh, :, 0:1] = (l_ref[hh, :, 0:1] * alpha
                                 + jnp.sum(p, -1, keepdims=True))
            acc_ref[hh] = acc_ref[hh] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[hh, :, 0:1] = m_cur

    @pl.when(j == nj - 1)
    def _active_and_finalize():
        for hh in range(nh):
            q = q_ref[0, 0, hh].astype(jnp.float32)        # (G, D)
            kn = nk_ref[0, 0, hh].astype(jnp.float32)      # (1, D)
            vn = nv_ref[0, 0, hh].astype(jnp.float32)      # (1, D)
            s = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)      # (G, 1)
            m_prev = m_ref[hh, :, 0:1]
            m_cur = jnp.maximum(m_prev, s)
            if has_sink:
                sk = sink_ref[0, hh].astype(jnp.float32).reshape(-1)[:, None]
                m_cur = jnp.maximum(m_cur, sk)
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_new = l_ref[hh, :, 0:1] * alpha + p
            if has_sink:
                l_new = l_new + jnp.exp(sk - m_cur)
            acc = acc_ref[hh] * alpha + p * vn
            o_ref[0, 0, hh] = (acc / l_new).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "soft_cap", "kv_scale", "interpret"))
def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, new_k: jnp.ndarray,
                           new_v: jnp.ndarray, layer: jnp.ndarray,
                           lens: jnp.ndarray, block_table: jnp.ndarray, *,
                           scale: float,
                           window: Optional[jnp.ndarray] = None,
                           soft_cap: Optional[float] = None,
                           sink: Optional[jnp.ndarray] = None,
                           kv_scale: Optional[float] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Ragged paged decode attention over the stacked block cache.

    q (B, Hq, D); k_pages/v_pages (L, N, Bs, Hkv, D); new_k/new_v
    (B, Hkv, D); lens (B,) prior lengths; block_table (B, max_blocks)
    logical→physical page map (entry 0 = null page). Returns (B, Hq, D).

    The WHOLE table rides scalar prefetch into SMEM — 4·(2 + B + B·max_blocks)
    bytes — and a v5e core has 1 MiB of it (:data:`PAGED_TABLE_SMEM_BYTES`).
    64 rows × 4,096 blocks does not fit; a caller that needs such tables
    must split the batch or page the table (ROADMAP B2's long contexts).
    """
    b, hq, d = q.shape
    hkv = k_pages.shape[3]
    bs = k_pages.shape[2]
    mb = block_table.shape[1]
    g = hq // hkv
    table_bytes = 4 * (2 + b + b * mb)
    if table_bytes > PAGED_TABLE_SMEM_BYTES:
        raise ValueError(
            f"paged decode kernel: block table of {b} rows x {mb} blocks "
            f"needs {table_bytes} B of SMEM scalar prefetch, over the "
            f"{PAGED_TABLE_SMEM_BYTES} B a v5e core can hold")

    vmem_budget = 4 * 1024 * 1024
    max_nh = max(1, min(8, vmem_budget // (bs * d * 2 * 2 * 2)))
    nh = 1
    for cand in range(max_nh, 0, -1):
        if hkv % cand == 0:
            nh = cand
            break
    hb = hkv // nh

    qr = q.reshape(b, hb, nh, g, d)
    sink_in = (sink.reshape(hb, nh, 1, g) if sink is not None
               else jnp.zeros((hb, nh, 1, g), jnp.float32))

    def q_map(bi, h, j, sc):
        return (bi, h, 0, 0, 0)

    def _live_page(bi, j, sc):
        pos_b = sc[2 + bi]
        last_live = jax.lax.max(
            jax.lax.div(jax.lax.max(pos_b - 1, 0), bs), 0)
        w = sc[1]
        first_live = jax.lax.select(
            w > 0, jax.lax.max(jax.lax.div(jax.lax.max(pos_b - w, 0), bs),
                               0), 0)
        jc = jax.lax.min(jax.lax.max(j, first_live), last_live)
        return sc[2 + b + bi * mb + jc]         # physical page id

    def kv_map(bi, h, j, sc):
        # pages (L, N, Bs, Hkv, D): full Bs rows, nh-head slab
        return (sc[0], _live_page(bi, j, sc), 0, h, 0)

    def nkv_map(bi, h, j, sc):
        return (bi, h, 0, 0, 0)

    def sink_map(bi, h, j, sc):
        return (h, 0, 0, 0)

    grid = (b, hb, mb)
    kernel = functools.partial(
        _paged_kernel, scale=scale, block_s=bs, nh=nh, kv_scale=kv_scale,
        soft_cap=soft_cap, has_sink=sink is not None)
    if window is None:
        window = jnp.zeros((), jnp.int32)
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(window, jnp.int32).reshape(1),
        lens.astype(jnp.int32),
        block_table.astype(jnp.int32).reshape(-1)])
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, nh, g, d), q_map),
                pl.BlockSpec((1, 1, bs, nh, d), kv_map),
                pl.BlockSpec((1, 1, bs, nh, d), kv_map),
                pl.BlockSpec((1, 1, nh, 1, d), nkv_map),
                pl.BlockSpec((1, 1, nh, 1, d), nkv_map),
                pl.BlockSpec((1, nh, 1, g), sink_map),
            ],
            out_specs=pl.BlockSpec((1, 1, nh, g, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((nh, g, d), jnp.float32),
                pltpu.VMEM((nh, g, 128), jnp.float32),
                pltpu.VMEM((nh, g, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hb, nh, g, d), q.dtype),
        interpret=interpret,
    )(scalars, qr, k_pages, v_pages,
      new_k.reshape(b, hb, nh, 1, d), new_v.reshape(b, hb, nh, 1, d),
      sink_in)
    return out.reshape(b, hq, d)


def paged_dispatch(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                   new_k: jnp.ndarray, new_v: jnp.ndarray, layer: jnp.ndarray,
                   lens: jnp.ndarray, block_table: jnp.ndarray, *,
                   scale: float, window: Optional[jnp.ndarray] = None,
                   soft_cap: Optional[float] = None,
                   sink: Optional[jnp.ndarray] = None,
                   kv_scale: Optional[float] = None,
                   interpret: bool = False) -> Optional[jnp.ndarray]:
    """Mesh-aware entry for the paged kernel: shard kv-heads over the
    model-parallel axes, matching the block-cache sharding
    P(None, None, None, ("ep","tp"), None) (modules/block_kv_cache.py).
    Returns None when the heads cannot be sharded over a >1 mp degree."""
    mesh = jax.sharding.get_abstract_mesh()
    b = q.shape[0]
    hkv = k_pages.shape[3]
    mp_axes = tuple(a for a in ("ep", "tp")
                    if a in mesh.axis_names and mesh.shape[a] > 1)
    mp = 1
    for a in mp_axes:
        mp *= mesh.shape[a]
    if mp > 1 and hkv % mp != 0:
        return None
    # batch rows split over dp (pages stay replicated across dp — the
    # block cache has no dp axis, block_cache_pspec)
    dp_axes = tuple(a for a in ("dp",)
                    if a in mesh.axis_names and mesh.shape[a] > 1
                    and b % mesh.shape[a] == 0)
    if not mp_axes and not dp_axes:
        return paged_decode_attention(
            q, k_pages, v_pages, new_k, new_v, layer, lens, block_table,
            scale=scale, window=window, soft_cap=soft_cap, sink=sink,
            kv_scale=kv_scale, interpret=interpret)

    if window is None:
        window = jnp.zeros((), jnp.int32)
    from jax.sharding import PartitionSpec as P
    mpx = mp_axes if mp_axes else None
    dp = dp_axes if dp_axes else None
    in_specs = [
        P(dp, mpx, None),                    # q
        P(None, None, None, mpx, None),      # k_pages
        P(None, None, None, mpx, None),      # v_pages
        P(dp, mpx, None),                    # new_k
        P(dp, mpx, None),                    # new_v
        P(),                                 # layer
        P(dp),                               # lens
        P(dp, None),                         # block_table
        P(),                                 # window
    ]
    args = [q, k_pages, v_pages, new_k, new_v, layer, lens, block_table,
            jnp.asarray(window, jnp.int32)]
    if sink is not None:
        in_specs.append(P(mpx))
        args.append(sink)

    def body(q, kp, vp, nk, nv, layer, lens, table, window, *rest):
        return paged_decode_attention(
            q, kp, vp, nk, nv, layer, lens, table, scale=scale,
            window=window, soft_cap=soft_cap,
            sink=rest[0] if rest else None, kv_scale=kv_scale,
            interpret=interpret)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(dp, mpx, None), check_vma=False)(*args)


def supports(spec, phase_t: int) -> bool:
    """Kernel admission (reference analog: TKG kernel enablement flags,
    models/config.py:417-567): single active token, no MLA (different head
    dims; the kernel streams K and V with one block shape), no chunked
    attention (the kernel masks by window, not chunk boundaries — llama4's
    chunked local layers take the XLA path)."""
    return (phase_t == 1 and spec.mla is None
            and spec.head_dim in (64, 128) and spec.attn_chunk == 0)
