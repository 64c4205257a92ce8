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

The PAGED kernel (``paged_decode_attention``, the serving path's) has no KV
grid at all. Its grid is the rows; for each the kernel itself loops over the
row's LIVE pages - from the window's first page to the last one holding a
prior token - so a call costs what the live context costs, whatever the
block table's width, and a dead table entry is never read. What lies where:

* SMEM (scalar prefetch): layer, window, every row's length and the whole
  block table (``PAGED_TABLE_SMEM_BYTES`` bounds it).
* HBM: the stacked pools, untouched (``memory_space=pl.ANY``); the layer
  and the physical page are indexed by hand.
* VMEM: two K and two V slots of ``pages`` pages each
  (``PAGED_KV_VMEM_BYTES`` together). A page with all of a shard's heads is
  one contiguous slab, so one async copy a page fills a slot while the other
  slot is computed on. ``pages`` follows the page's bytes, a cap on a
  block's score tile and a cap on unrolled copies (``paged_block_plan``):
  nothing of it is configured.
* The copies of a call are ONE stream over all its rows (ISSUE 66, as
  ``ops/mla_decode.py``'s since PR 53): under a row's LAST block the kernel
  starts the FIRST block of the next live row into the slot that block frees,
  and that row's grid step awaits it and does not start it again. Slots,
  semaphores and the count of blocks walked (a block's slot is its parity)
  carry across grid steps - the grid is stated sequential - a row of length
  0 neither starts nor awaits a copy, and only the call's first live row
  starts its own first block (``prefetch=across-rows`` in the note).
* MXU: a slot is a (tokens x kv heads, lanes of a head) matrix as it lies -
  128 lanes, or 256 - and all heads score in one block-diagonal matmul
  against it (heads of 64 lanes sit two to a 128-lane row:
  ``paged_pool_fold``). Operands are bf16 wherever that loses
  nothing: q . k from bf16 operands accumulates in fp32; p stays fp32 - it
  goes in as three bf16 pieces in one pass over V (``_split_dot``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38

#: largest scalar-prefetch operand (layer, window, lens, block table) the paged
#: kernel is asked to stage. v5e SMEM is 1 MiB and the kernel's own scoped
#: use takes a few KiB of it: compiling for the v5e topology, 1,040,408 B
#: went through and 1,044,744 B was refused (RESOURCE_EXHAUSTED, space=smem).
PAGED_TABLE_SMEM_BYTES = 1_040_408

#: VMEM the paged kernel spends on its two K and two V slots together (the
#: scoped limit of a v5e kernel is 16 MiB; scores and their copies take ~2).
PAGED_KV_VMEM_BYTES = 4 * 1024 * 1024
#: most elements of a compute block's (query heads, tokens x a shard's kv
#: rows) fp32 score tile, 32 vregs: the MXU's work is per column, live page or
#: not, and the softmax is unrolled over the tile: it bounds the code as well.
PAGED_SCORE_TILE_ELEMENTS = 32 * 1024
#: most pages of one compute block: their copies are unrolled in the kernel.
PAGED_BLOCK_PAGES = 16

#: the paged kernels' grid, the rows, runs in turn: slots, semaphores and the
#: count of blocks walked carry from a row to the next
_ROWS_IN_TURN = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

_NT = (((1,), (1,)), ((), ()))        # (M, K) x (N, K) -> (M, N)
_NN = (((1,), (0,)), ((), ()))        # (M, K) x (K, N) -> (M, N)


def _bf16_exact(dtype) -> bool:
    """Every value of ``dtype`` is a bfloat16 value (bf16 and the fp8s)."""
    dtype = jnp.dtype(dtype)
    return dtype == jnp.bfloat16 or (dtype.itemsize == 1
                                     and jnp.issubdtype(dtype, jnp.floating))


class PagedPlan(NamedTuple):
    """What one call of the paged kernel runs with (:func:`paged_block_plan`)."""
    pages: int          # pages a compute block gathers
    fold: int           # kv heads sharing one row of a page
    hkv: int            # the kernel's kv rows a token: a shard's heads / fold
    g: int              # query rows a kv row: group size x fold
    d: int              # lanes of a kv row: head_dim x fold
    form: str = "mxu-blockdiag"     # the inner score loop's shape

    def note(self, stored: bool) -> str:
        """The engagement record's text (``kernel_mode.note``): the plan,
        where a fold lives - ``stored`` (the pool was allocated so,
        ``block_kv_cache.pool_page``) or ``call`` (a pool handed over a
        head a slot, which the call reshapes: a relayout of the whole pool
        wherever the device tiles the two shapes differently) - and, last,
        the walk: the rows' blocks are one stream of copies."""
        where = "" if self.fold == 1 else " stored" if stored else " call"
        return (f"pages={self.pages} heads={self.hkv * self.fold} "
                f"form={self.form} fold={self.fold}{where} "
                "prefetch=across-rows")


#: fewer kv rows a token than this (the second-minor extent of a 32-bit
#: tile) do not fill the rows of a tile
PAGED_ROW_TILE = 8


def paged_pool_fold(hkv: int, d: int) -> int:
    """How many neighbouring kv heads (of a shard's ``hkv``) share one row
    of a page in the kernel's view of the pool - and, because the pool is
    ALLOCATED by this rule (``block_kv_cache.pool_page``, the one place
    that decides a page's shape), in the pool as it is stored. Heads
    narrower than a vreg go in side by side, the page's bytes as they lie
    (a manual copy cannot slice a 64-lane minor dimension out of a tiled
    array); a FEW heads (2 to 7) of whole vregs share one row too, ``hkv x
    d`` lanes (a page of 2 heads is tiled ``(2, 128)`` and the ``(tokens x
    heads, lanes)`` view of it was a relayout of the whole pool a call), and
    so do 9 to 15 (10 rows of 128 lanes a token, a differential stack's
    20 heads of 64 paired: the device kept such a page tokens-minor and
    every decode step copied both pools, 4.9 GB of temps by AOT, PR 54;
    whole tiles of rows, 8 and 16, lie as declared, and more than 16 are
    rounded up to whole tiles by ``block_kv_cache.pool_kv_heads``).
    Heads that do not fold evenly (an odd count of narrow heads, 96 lanes)
    stay a head a row: 1."""
    if d < 128 and 128 % d == 0:
        fold = 128 // d
    elif d % 128 == 0 and 1 < hkv < 2 * PAGED_ROW_TILE \
            and hkv % PAGED_ROW_TILE:
        fold = hkv
    else:
        fold = 1
    return fold if hkv % fold == 0 else 1


def _blockdiag_plan(bs: int, hkv: int, g: int, d: int, kv_dtype,
                    mb: int) -> PagedPlan:
    """The block-diagonal walk of a call of this geometry (``hkv`` a shard's
    kv heads, ``g`` query heads a kv head, ``mb`` the table's width); what
    :func:`paged_block_plan`, at this file's end, gives every geometry but
    kv rows of whole query tiles.

    With ``fold`` heads to a row (:func:`paged_pool_fold`) a page is a
    ``(bs * hkv / fold, fold * d)`` matrix, and a compute block is ``pages``
    of them: the most that (a) four slots (K and V, double-buffered) fit
    :data:`PAGED_KV_VMEM_BYTES`, so a 1-byte item doubles it, (b) keep the
    block's score tile (query heads x tokens x kv rows) under
    :data:`PAGED_SCORE_TILE_ELEMENTS`, (c) :data:`PAGED_BLOCK_PAGES` and the
    table allow."""
    fold = paged_pool_fold(hkv, d)
    hkv, g, d = hkv // fold, g * fold, d * fold
    page_bytes = bs * hkv * d * jnp.dtype(kv_dtype).itemsize
    pages = min(PAGED_KV_VMEM_BYTES // (4 * page_bytes),
                PAGED_SCORE_TILE_ELEMENTS // (hkv * g * bs * hkv),
                PAGED_BLOCK_PAGES, mb)
    return PagedPlan(max(1, pages), fold, hkv, g, d)


def _split_dot(x, w, dims, exact: bool):
    """fp32-accurate ``x . w`` on the MXU. Where every value of ``w`` is a
    bf16 value (``exact``), an fp32 ``x`` goes in as its three bf16 pieces
    stacked along M (hi + mid + lo == x to the last bit), so ONE pass over
    ``w`` — the big operand, the MXU's weights — gives the products a
    six-pass fp32 matmul would; otherwise the fp32 matmul itself."""
    m = x.shape[0]
    if not exact or (x.dtype != jnp.bfloat16 and m % 8):
        return jax.lax.dot_general(
            x.astype(jnp.float32), w.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    w = w.astype(jnp.bfloat16)
    if x.dtype == jnp.bfloat16:
        return jax.lax.dot_general(x, w, dims,
                                   preferred_element_type=jnp.float32)
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    lo = rest - mid
    out = jax.lax.dot_general(
        jnp.concatenate([hi, mid, lo], axis=0).astype(jnp.bfloat16), w, dims,
        preferred_element_type=jnp.float32)
    return (out[2 * m:] + out[m:2 * m]) + out[:m]


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


def _model_parallel(mesh):
    """The ambient mesh's model-parallel axes that are wider than one, and
    their product: what kv heads are sharded over."""
    axes = tuple(a for a in ("ep", "tp")
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    mp = 1
    for a in axes:
        mp *= mesh.shape[a]
    return axes, mp


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
    mp_axes, mp = _model_parallel(mesh)
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


def _row_walk(sc_ref, r, *, bs: int, mb: int, pages: int):
    """What the paged kernels walk of row ``r``, from the scalars in SMEM
    (which hold EVERY row's length and table): its prior length, its compute
    blocks of ``pages`` pages (0: nothing cached) and ``(first live page,
    last live page, offset of its table)`` - the window's first page to the
    last one holding a prior token."""
    nb = pl.num_programs(0)
    w = sc_ref[1]
    pos = sc_ref[2 + r]
    last_live = jax.lax.div(jnp.maximum(pos - 1, 0), bs)
    first_live = jnp.where(w > 0, jax.lax.div(jnp.maximum(pos - w, 0), bs), 0)
    n_pages = jnp.where(pos > 0, last_live - first_live + 1, 0)
    return (pos, jax.lax.div(n_pages + pages - 1, pages),
            (first_live, last_live, 2 + nb + r * mb))


def _next_live_row(sc_ref, r):
    """The first row at or after ``r`` with a cached token (the row count:
    none): a row of length 0 neither starts nor awaits a copy, and the
    stream of copies skips over it."""
    nb = pl.num_programs(0)
    return jax.lax.while_loop(
        lambda r: jnp.logical_and(r < nb, sc_ref[2 + r] == 0),
        lambda r: r + 1, r)


def _row_stream(sc_ref, count, start, *, bs: int, mb: int, pages: int):
    """This grid step's place in the call's ONE stream of copies. Returns the
    row's prior length, its compute blocks, its walk (:func:`_row_walk`) and
    ``slot_of(i)``, which the block loop calls once a block, BEFORE it awaits
    block ``i``: it starts the stream's next block - this row's, or under this
    row's last the next live row's first - into the slot block ``i - 1`` left
    (``start(walk, block, slot)`` is the kernel's own) and returns block
    ``i``'s slot, the parity of the blocks walked so far (``count``, SMEM,
    carried from row to row). Only the call's first live row starts its own
    first block; the last live row starts nothing."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    walk_of = functools.partial(_row_walk, sc_ref, bs=bs, mb=mb, pages=pages)
    pos, n_blocks, own = walk_of(b)
    after = _next_live_row(sc_ref, b + 1)
    then = walk_of(after)[2]        # read only where ``after`` is a row

    @pl.when(b == 0)
    def _open():
        count[0] = 0
        row = _next_live_row(sc_ref, 0)
        pl.when(row < nb)(lambda: start(walk_of(row)[2], 0, 0))

    done = count[0]                 # blocks the rows before this one walked
    count[0] = done + n_blocks

    def slot_of(i):
        slot = jax.lax.rem(done + i, 2)
        last = i + 1 == n_blocks

        @pl.when(jnp.logical_or(jnp.logical_not(last), after < nb))
        def _next():
            start(tuple(jnp.where(last, x, y) for x, y in zip(then, own)),
                  jnp.where(last, 0, i + 1), 1 - slot)
        return slot
    return pos, n_blocks, own, slot_of


def _paged_kernel(sc_ref, q_ref, nk_ref, nv_ref, sink_ref, *rest,
                  scale: float, bs: int, mb: int, hkv: int, g: int,
                  soft_cap: Optional[float], has_sink: bool,
                  kv_scale: Optional[float], selected: bool = False):
    """Ragged PAGED decode attention (reference: the DMA-skipping TKG
    attention over the block layout, attention_base.py:1186-1382 +
    block_kv_cache_manager.py:183-267). One grid step is one ROW; the walk
    over its KV is a loop inside the step, as long as the row's live pages;
    the rows' blocks are ONE stream of copies (the module docstring): the
    step starts the next live row's first block under its own last one, and
    ``count`` (SMEM), the slots and their semaphores carry to the next step.

    Scalar prefetch (SMEM): [layer, window, len_0..len_{B-1},
    table_{b=0,j=0}.., table_{B-1,mb-1}]. ``k_hbm`` / ``v_hbm`` are the
    whole stacked pools (L, N, bs * hkv, d), left in HBM; a compute block
    is ``pages`` pages copied by hand (one async copy a page, page ids read
    from the table) into one of two VMEM slots while the other slot is
    computed on. Table entries before the window's first page or past the
    last live page are never read, so neither the time nor the result
    depends on the table's width.

    All heads score in ONE matmul: row ``c`` of a slot is token ``c // hkv``
    of kv row ``c % hkv`` and query row ``r`` belongs to kv row ``r // g``,
    so K and V go to the MXU as they lie, lane-dense, with no relayout, and
    the scores off that block diagonal are masked (the MXU is idle
    otherwise, and the softmax sees (Hq, columns) full vregs).

    ``selected`` (a learned sparse selection, ``model_base.SparseSpec``):
    one more input in front of the pools, ``sel_ref`` (1, compute blocks,
    columns of a block) float32, > 0 where the row's query attends that
    column's token; a column is attended only if it is live AND selected,
    and the active token joins only if its own flag, one more scalar a row
    behind the table, is set. The walk is the live pages' all the same."""
    if selected:
        sel_ref, *rest = rest
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, count = rest
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = sc_ref[0]
    w = sc_ref[1]
    _, pages, page_rows, d = kbuf.shape
    exact = _bf16_exact(kbuf.dtype)
    hq, cols = hkv * g, pages * page_rows

    def page_copies(walk, i, slot):
        first, last, table0 = walk
        for p in range(pages):
            j = first + i * pages + p
            page = sc_ref[table0 + jnp.minimum(j, last)]
            yield p, j <= last, (
                pltpu.make_async_copy(k_hbm.at[layer, page], kbuf.at[slot, p],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page], vbuf.at[slot, p],
                                      sem.at[1, slot]))

    def start(walk, i, slot):
        for p, live, (kc, vc) in page_copies(walk, i, slot):
            @pl.when(live)
            def _fetch():
                kc.start()
                vc.start()

            @pl.when(jnp.logical_not(live))
            def _blank():
                # a slot's page past the row's end is computed on (masked):
                # its V must be finite, whatever the slot held before
                vbuf[slot, p] = jnp.zeros((page_rows, d), vbuf.dtype)

    def wait(i, slot):
        for p, live, (kc, vc) in page_copies(own, i, slot):
            @pl.when(live)
            def _landed():
                kc.wait()
                vc.wait()

    pos, n_blocks, own, slot_of = _row_stream(sc_ref, count, start, bs=bs,
                                              mb=mb, pages=pages)
    first_live = own[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0)
    tok = jax.lax.div(col, hkv)
    mine = jax.lax.rem(col, hkv) == jax.lax.div(row, g)
    q = q_ref[0]
    s_scale = scale * kv_scale if kv_scale is not None else scale

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = slot_of(i)       # and the stream's next block is started
        wait(i, slot)
        kpos = (first_live + i * pages) * bs + tok
        valid = jnp.logical_and(mine, jnp.logical_and(
            kpos < pos, jnp.logical_or(w == 0, pos - kpos < w)))
        if selected:
            # no window with a selection: block i starts at page i x pages
            valid = jnp.logical_and(valid, sel_ref[0, pl.ds(i, 1), :] > 0)
        s = _split_dot(q, kbuf[slot].reshape(cols, d), _NT, exact) * s_scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        s = jnp.where(valid, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)              # fp32, never rounded to bf16
        return (m_cur, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + _split_dot(p, vbuf[slot].reshape(cols, d), _NN,
                                         exact))

    m_prev, l_prev, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((hq, 1), NEG_INF, jnp.float32),
        jnp.zeros((hq, 1), jnp.float32), jnp.zeros((hq, d), jnp.float32)))

    # the active token joins in registers: its score the softmax, its V the
    # accumulator (the pools' V is stored / kv_scale, the active V is not)
    s = jnp.sum(q.astype(jnp.float32) * nk_ref[0].astype(jnp.float32),
                axis=-1, keepdims=True) * scale
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    if selected:
        s = jnp.where(sc_ref[2 + nb + nb * mb + b] > 0, s, NEG_INF)
    m_cur = jnp.maximum(m_prev, s)
    if has_sink:
        # learned per-head sink joins the denominator only
        # (reference: modules/attention/sink.py)
        sk = sink_ref[...]
        m_cur = jnp.maximum(m_cur, sk)
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_new = l_prev * alpha + p
    if has_sink:
        l_new = l_new + jnp.exp(sk - m_cur)
    if kv_scale is not None:
        acc = acc * kv_scale
    o_ref[0] = ((acc * alpha + p * nv_ref[0].astype(jnp.float32))
                / l_new).astype(o_ref.dtype)


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
                           select: Optional[jnp.ndarray] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Ragged paged decode attention over the stacked block cache.

    q (B, Hq, D); k_pages/v_pages (L, N, Bs, Hkv, D) - or the same bytes
    already folded, (L, N, Bs, Hkv / fold, fold * D) with ``fold`` of
    :func:`paged_pool_fold`, which is how the application stores it
    (``block_kv_cache.pool_page``): the reshape below is then a bitcast,
    where a pool handed over a head a slot may cost a relayout of the whole
    pool a call; new_k/new_v
    (B, Hkv, D); lens (B,) prior lengths; block_table (B, max_blocks)
    logical->physical page map (entry 0 = null page); select (B, max_blocks
    x Bs) bool, optional (a learned sparse selection; no window with it):
    the positions each row's query attends, its own position among them -
    every other cached token is walked and left out. Returns (B, Hq, D).

    The grid is the rows. For each, the kernel walks the pages from the
    window's first to the last live one in compute blocks of ``pages`` pages
    (:func:`paged_block_plan`, which also says whether a block is scored
    block-diagonally or kv row by kv row): a call's time follows the live
    context, not the table's width. In SMEM: layer, window, lengths and the
    WHOLE table (:data:`PAGED_TABLE_SMEM_BYTES`: 64 rows x 4,096 blocks do
    not fit, ROADMAP B2); a page is one async copy from HBM into a slot."""
    b, hq, d = q.shape
    bs = k_pages.shape[2]
    mb = block_table.shape[1]
    fold = k_pages.shape[4] // d              # > 1: the pool came folded
    hkv = k_pages.shape[3] * fold
    g = hq // hkv
    table_bytes = 4 * (2 + b + b * mb)
    if table_bytes > PAGED_TABLE_SMEM_BYTES:
        raise ValueError(
            f"paged decode kernel: block table of {b} rows x {mb} blocks "
            f"needs {table_bytes} B of SMEM scalar prefetch, over the "
            f"{PAGED_TABLE_SMEM_BYTES} B a v5e core can hold")
    pages, fold_k, hkv_k, g_k, d_k, form = paged_block_plan(
        bs, hkv, g, d, k_pages.dtype, mb)
    assert fold in (1, fold_k), (fold, fold_k)     # folded as the plan folds
    fold = fold_k
    # a page as the matrix it is in memory: (tokens x kv rows, lanes)
    k_pages, v_pages = (x.reshape(x.shape[:2] + (bs * hkv_k, d_k))
                        for x in (k_pages, v_pages))
    # query rows and the active K / V, a row a query head; with heads folded
    # a row holds its own head's lanes and zeros elsewhere, and its output
    # is read back from those lanes
    new_k, new_v = (jnp.repeat(x, g, axis=1) for x in (new_k, new_v))
    own = ((np.arange(hq) // g) % fold)[:, None] == np.arange(fold)

    def place(x):                                        # (B, Hq, d) -> d_k
        if fold == 1:
            return x
        return jnp.where(own[None, :, :, None], x[:, :, None, :],
                         jnp.zeros((), x.dtype)).reshape(b, hq, d_k)

    sink_in = (sink.astype(jnp.float32).reshape(hq, 1) if sink is not None
               else jnp.zeros((hq, 1), jnp.float32))
    row_spec = pl.BlockSpec((1, hq, d_k), lambda bi, sc: (bi, 0, 0))
    kernel = functools.partial(
        _paged_kernel, scale=scale, bs=bs, mb=mb, hkv=hkv_k, g=g_k,
        kv_scale=kv_scale, soft_cap=soft_cap, has_sink=sink is not None,
        selected=select is not None)
    if window is None:
        window = jnp.zeros((), jnp.int32)
    scalars = [
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(window, jnp.int32).reshape(1),
        lens.astype(jnp.int32),
        block_table.astype(jnp.int32).reshape(-1)]
    sel_in, sel_spec = [], []
    if select is not None:
        # a block's columns as the kernel counts them (token-major, a
        # token's kv rows neighbours), blocks padded to whole ones; the
        # active token's own flag rides behind the table
        n_blk = -(-mb // pages)
        cols = pages * bs * hkv_k
        own_flag = jnp.take_along_axis(
            select, jnp.minimum(lens.astype(jnp.int32), mb * bs - 1)[:, None],
            axis=1)[:, 0]
        scalars.append(own_flag.astype(jnp.int32))
        sel = jnp.pad(select, ((0, 0), (0, n_blk * pages * bs - mb * bs)))
        sel_in = [jnp.repeat(sel.astype(jnp.float32), hkv_k, axis=1)
                  .reshape(b, n_blk, cols)]
        sel_spec = [pl.BlockSpec((1, n_blk, cols), lambda bi, sc: (bi, 0, 0))]
    scalars = jnp.concatenate(scalars)
    if form == PAGED_ROWS_FORM:     # kv row by kv row: the body at the end
        return _paged_rows_call(
            scalars, q, new_k, new_v, sink_in, select, k_pages, v_pages,
            pages=pages, hkv=hkv_k, mb=mb, scale=scale, soft_cap=soft_cap,
            has_sink=sink is not None, kv_scale=kv_scale, interpret=interpret)
    slot = (2, pages, bs * hkv_k, d_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                row_spec, row_spec, row_spec,
                pl.BlockSpec((hq, 1), lambda bi, sc: (0, 0)),
                *sel_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM(slot, k_pages.dtype),
                pltpu.VMEM(slot, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, d_k), q.dtype),
        compiler_params=_ROWS_IN_TURN,
        interpret=interpret,
    )(scalars, place(q), place(new_k), place(new_v), sink_in, *sel_in,
      k_pages, v_pages)
    if fold > 1:
        out = jnp.sum(jnp.where(own[None, :, :, None],
                                out.reshape(b, hq, fold, d),
                                jnp.zeros((), out.dtype)), axis=2)
    return out


def paged_dispatch(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                   new_k: jnp.ndarray, new_v: jnp.ndarray, layer: jnp.ndarray,
                   lens: jnp.ndarray, block_table: jnp.ndarray, *,
                   scale: float, window: Optional[jnp.ndarray] = None,
                   soft_cap: Optional[float] = None,
                   sink: Optional[jnp.ndarray] = None,
                   kv_scale: Optional[float] = None,
                   select: Optional[jnp.ndarray] = None,
                   interpret: bool = False) -> Optional[jnp.ndarray]:
    """Mesh-aware entry for the paged kernel: shard kv-heads over the
    model-parallel axes, matching the block-cache sharding
    P(None, None, None, ("ep","tp"), None) (modules/block_kv_cache.py).
    Returns None when the heads cannot be sharded over a >1 mp degree, and
    for a ``select`` (one chip only) on a mesh wider than one."""
    mesh = jax.sharding.get_abstract_mesh()
    b = q.shape[0]
    hkv = k_pages.shape[3]
    mp_axes, mp = _model_parallel(mesh)
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
            kv_scale=kv_scale, select=select, interpret=interpret)
    if select is not None:
        return None

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


def paged_dispatch_plan(hq: int, d: int, k_pages: jnp.ndarray,
                        mb: int) -> Optional[PagedPlan]:
    """The plan :func:`paged_dispatch` runs a pool (as stored, or already
    folded) of ``d``-wide heads with under the ambient mesh - a shard's
    heads - for the engagement record and for a caller that folds the pool
    itself; None where the dispatch declines."""
    _, mp = _model_parallel(jax.sharding.get_abstract_mesh())
    hkv = k_pages.shape[3] * (k_pages.shape[4] // d)
    if hkv % mp:
        return None
    return paged_block_plan(k_pages.shape[2], hkv // mp, hq // hkv, d,
                            k_pages.dtype, mb)


def supports(spec, phase_t: int, paged: bool = False) -> bool:
    """Kernel admission (reference analog: TKG kernel enablement flags,
    models/config.py:417-567): single active token, no MLA here (its paged
    pool holds a latent row a token, not heads, and a decode step attends in
    the latent space on a kernel of its own, ``ops/mla_decode.py``; over the
    contiguous cache its expanded K and V heads differ in width and take the
    XLA path), no chunked attention (the kernel masks by window, not chunk boundaries — llama4's
    chunked local layers take the XLA path). Heads of 64 lanes (on the paged
    path two to a 128-lane row of a page, ``paged_pool_fold``) and of 128;
    the PAGED kernel (``paged``) also heads of 256, a kv row of two vregs:
    it takes the lanes of a row from the arrays it is handed."""
    return (phase_t == 1 and spec.mla is None and spec.attn_chunk == 0
            and spec.head_dim in ((64, 128, 256) if paged else (64, 128)))


# ---------------------------------------------------------------------------
# The paged kernel's second inner loop: kv row by kv row (ISSUE 57). It lives
# at the file's end because a Pallas body's serialised form holds the line
# numbers of its whole call stack: every line above ``paged_dispatch_plan``
# that the block-diagonal call passes through is where it was, so the cells
# that keep that form find their programs in the compile cache.
# ---------------------------------------------------------------------------

#: ``PagedPlan.form`` of a call scored kv row by kv row
PAGED_ROWS_FORM = "mxu-kv-rows"


def paged_block_plan(bs: int, hkv: int, g: int, d: int, kv_dtype,
                     mb: int) -> PagedPlan:
    """How the paged kernel walks a call of this geometry (``hkv`` a shard's
    kv heads, ``g`` query heads a kv head, ``mb`` the table's width): chosen
    from what the call can observe - page geometry and item size - and from
    nothing else.

    With ``fold`` heads to a row (:func:`paged_pool_fold`) a page is a
    ``(bs * hkv / fold, fold * d)`` matrix, and a compute block is ``pages``
    of them: the most that (a) four slots (K and V, double-buffered) fit
    :data:`PAGED_KV_VMEM_BYTES`, so a 1-byte item doubles it, (b) keep the
    block's score tile under :data:`PAGED_SCORE_TILE_ELEMENTS`, (c)
    :data:`PAGED_BLOCK_PAGES` and the table allow.

    The score tile has two shapes. ``mxu-blockdiag``: all query heads
    against all rows of the block in one matmul, what is off the block
    diagonal masked - query heads x tokens x kv rows elements, right where a
    token has ONE kv row or a kv row one query row (the MXU is idle
    otherwise). ``mxu-kv-rows`` (:data:`PAGED_ROWS_FORM`): where a head of
    128 lanes has a row of the page to itself (fold 1), a token has whole
    tiles of kv rows (:data:`PAGED_ROW_TILE`), each has whole tiles of query
    rows and the pool is bf16 or 32-bit (what :func:`_paged_rows_kernel` can
    read a kv row out of: Mosaic refuses the 32-bit view of a 256-lane
    slot), a kv row's ``g`` query rows are scored against its own ``pages x
    bs`` rows of the slot - query heads x tokens elements, ``hkv`` times
    fewer, so ``pages`` rises by as much.

    The clock (``scripts/paged_decode_time.py``, PR 57, one v5e: the call
    alone, 32 rows of ~2k / ~6k tokens, ms a call and % of
    ``paged_decode_min_bytes`` at 819 GB/s; 128 query heads over 8 kv heads
    of 128 lanes, bf16; the window-4096 column at ~6k tokens)::

        form, pages, kv rows a loop turn      2k           6k        6k w4096
        block-diagonal, 1 page             1.442 (23)  4.107 (24)  2.779 (24)
        copies and waits alone, 8 pages    0.454 (73)  1.165 (85)  0.813 (81)
        BY PHASE, pairs read, 8, 8 [kept]  0.479 (69)  1.194 (83)  0.853 (77)
        by phase, pairs read, 8, 2         0.648 (51)  1.680 (59)  1.197 (55)
        by phase, pairs read, 16, 8        0.484 (68)  1.196 (82)  0.873 (75)
        by phase, float32 copy, 8, 8       0.527 (63)  1.325 (74)  0.947 (70)
        by phase, float32 copy, 8, 1       1.078 (31)  2.882 (34)  2.034 (32)
        row by row, pairs read, 16, 8      0.621 (53)  1.505 (66)  1.121 (59)
        row by row, pairs read, 8, 8       0.899 (37)  2.375 (42)  1.688 (39)
        row by row, float32 copy, 8, 8     0.987 (34)  2.626 (38)  1.859 (35)
        row by row, float32 copy, 8, 1     1.266 (26)  3.449 (29)  2.417 (27)

    "Row by row" scored, soft-maxed and summed one kv row before the next: a
    chain of MXU and reduction latencies a row that no unrolling hid. "By
    phase" (kept) scores EVERY kv row, runs one softmax over all query rows
    and then sums every kv row: with the kv rows of a phase unrolled it
    hides under its own copies. The float32 copy of a block (64 stores and
    64 strided loads a page) lost 12 % to the 32-bit view of row pairs, 16
    pages bought nothing over 8, and a loop over kv rows instead of unrolled
    copies cost 40-140 %. The other cells' geometries under the form they
    keep, same script: OLMoE (16 x 1) 0.823 (80) / 2.243 (88), granite (4
    rows x 8 after the fold) 0.343 (48) / 0.805 (61), olmo-hybrid (32 x 1)
    1.793 (73) / 5.162 (76).

    The rows' blocks as ONE stream of copies (ISSUE 66; same script, PR 66,
    parent and change in one call on one v5e: the call alone, 32 rows, ms a
    call parent | change and the change's % of its bytes; kv rows x query
    rows a kv row after the fold, pages a block)::

        geometry                         ~2k tokens a row      ~6k
        command-a-plus (kv-row form, 8)  0.486 | 0.446 (74)    1.191 | 1.157 (85)
          inside a window of 4096        0.475 | 0.450 (74)    0.853 | 0.825 (80)
        OLMoE (16 x 1, 4)                0.823 | 0.796 (82)    2.242 | 2.213 (89)
        granite (4 x 8, 8)               0.337 | 0.321 (51)    0.801 | 0.789 (62)
        olmo-hybrid (32 x 1, 1)          1.794 | 1.774 (74)    5.150 | 5.142 (77)
        smallthinker (1 x 28, 16)        0.363 | 0.349 (47)    0.839 | 0.827 (60)
          a ring of 4096                 0.361 | 0.359 (46)    0.626 | 0.630 (52)
        keye (1 x 32, 16, a selection)   0.318 | 0.297 (55)    0.700 | 0.675 (73)
        phi4-flash (1 x 40, 12) at ~5k                         1.297 | 1.233 (83)
          a ring of 512                                        0.288 | 0.241 (43)
        nemotron (1 x 32, 16) at ~0.5k   0.139 | 0.136 (16)    ~1k: 0.176 | 0.182 (23)

    The lever is worth 0.4-2.0 us a row (ten 128-lane pairs a row and the
    kv-row form the most, 28-32 query rows over one kv row the least), not
    one figure. A ring of three or four slots under a cursor (the latent
    kernel's) bought nothing - keye at ~6k 0.692 | 0.694 | 0.690 at 2 | 3 | 4
    slots, phi4-flash 1.239 | 1.219 | 1.210 and its ring 0.247 | 0.232 |
    0.239, smallthinker 0.832 | 0.840 | 0.839, the clock itself +-2 % from
    call to call - so two slots and their parity stay. What is left, by the
    same walk stopped short: the copies and waits ALONE read keye 0.615 (80
    %), smallthinker 0.618, granite 0.610 at ~6k and phi4-flash 1.214 (85 %);
    the block's arithmetic with NO copy keye 0.366, granite 0.470,
    smallthinker 0.523 (28 query rows are not whole sublane tiles, so
    :func:`_split_dot` takes the six-pass float32 matmul for ``p . V``),
    phi4-flash 0.689: the whole call is its arithmetic PLUS 0.5-0.8 us a
    block, the 32 starts and 32 waits of 16 pages, which run on the scalar
    core in line with the block and hide under nothing; and a row of ONE
    block (nemotron's, granite's cell) costs ~4 us whatever its copy does.
    Fewer, larger copies where a row's pages lie side by side in the pool is
    the lever no clock has met."""
    plan = _blockdiag_plan(bs, hkv, g, d, kv_dtype, mb)
    kv_dtype = jnp.dtype(kv_dtype)
    if (plan.fold > 1 or d != 128 or hkv % PAGED_ROW_TILE
            or g % PAGED_ROW_TILE
            or not (kv_dtype == jnp.bfloat16 or kv_dtype.itemsize == 4)):
        return plan
    pages = min(PAGED_KV_VMEM_BYTES // (4 * bs * hkv * d * kv_dtype.itemsize),
                PAGED_SCORE_TILE_ELEMENTS // (hkv * g * bs),
                PAGED_BLOCK_PAGES, mb)
    return plan._replace(pages=max(1, pages), form=PAGED_ROWS_FORM)


def _paged_rows_kernel(sc_ref, q_ref, nk_ref, nv_ref, sink_ref, *rest,
                       scale: float, bs: int, mb: int, pages: int,
                       soft_cap: Optional[float], has_sink: bool,
                       kv_scale: Optional[float], selected: bool):
    """:func:`_paged_kernel`'s walk - one grid step a ROW, its live pages
    copied by hand ``pages`` a block into one of two slots, the rows' blocks
    ONE stream of copies, the table and lengths in SMEM, dead entries never
    read, the active token joined in registers - with the block scored kv
    row by kv row, by phase.

    A slot is ``(pages x bs x hkv, d)`` as the pages lie: row ``c`` is token
    ``c // hkv`` of kv row ``c % hkv``. Kv row ``r``'s tokens are every
    ``hkv``-th row from ``r``, and Mosaic reads rows at a stride from 32-bit
    data only: a 32-bit pool is read so; of a bf16 pool the slot's 32-bit
    VIEW holds kv rows ``2j`` (low halves) and ``2j + 1`` of a token in ONE
    row, read every ``hkv / 2``-th row and parted by a shift and a mask.
    Query rows, running max / sum and the accumulator are ``(hkv, g, ..)``:
    a kv row is a LEADING index.

    A block goes by phase: every kv row's scores into ``s_ref``, ONE
    softmax over all query rows, every kv row's ``p . V`` - no kv row waits
    on its own softmax, and with a tile of kv rows unrolled a loop turn the
    MXU's passes follow one another (the clock: :func:`paged_block_plan`).
    The pages of a block are copied in a loop, not in unrolled copies.

    The mathematics is the block-diagonal body's: scores from bf16 operands
    accumulated in float32 (:func:`_split_dot`; a float32 pool the
    ``HIGHEST`` matmul), ``p`` float32 into ``p . V`` as three bf16 pieces,
    a page past the row's end computed on zeros and masked. ``selected``:
    ``sel_ref`` (1, blocks, tokens of a block) float32, > 0 where the row's
    query attends the token."""
    if selected:
        sel_ref, *rest = rest
    (k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, count, m_ref, l_ref, acc_ref,
     s_ref) = rest
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = sc_ref[0]
    w = sc_ref[1]
    hkv, g, d = acc_ref.shape
    page_rows, toks = bs * hkv, pages * bs
    exact = _bf16_exact(kbuf.dtype)
    s_scale = scale * kv_scale if kv_scale is not None else scale

    def each_page(walk, i, slot, live_do, dead_do=None):
        first, last, table0 = walk

        def page(p, carry):
            j = first + i * pages + p
            at = sc_ref[table0 + jnp.minimum(j, last)]
            into = pl.ds(pl.multiple_of(p * page_rows, page_rows), page_rows)

            @pl.when(j <= last)
            def _live():
                live_do(pltpu.make_async_copy(
                    k_hbm.at[layer, at], kbuf.at[slot, into], sem.at[0, slot]))
                live_do(pltpu.make_async_copy(
                    v_hbm.at[layer, at], vbuf.at[slot, into], sem.at[1, slot]))

            if dead_do is not None:
                pl.when(j > last)(lambda: dead_do(into))
            return carry
        jax.lax.fori_loop(0, pages, page, 0)

    def start(walk, i, slot):
        def blank(into):
            # a slot's page past the row's end is computed on (masked): its
            # V must be finite, whatever the slot held before
            vbuf[slot, into, :] = jnp.zeros((page_rows, d), vbuf.dtype)
        each_page(walk, i, slot, lambda copy: copy.start(), blank)

    def wait(i, slot):
        each_page(own, i, slot, lambda copy: copy.wait())

    pos, n_blocks, own, slot_of = _row_stream(sc_ref, count, start, bs=bs,
                                              mb=mb, pages=pages)
    first_live = own[0]

    if kbuf.dtype == jnp.bfloat16:
        turns, held = hkv // 2, PAGED_ROW_TILE // 2

        def rows_of(buf, slot, j):
            """(kv row, its (toks, d) rows of a slot) for rows 2j, 2j + 1."""
            x = buf.bitcast(jnp.uint32)[slot, pl.ds(j, toks, stride=hkv // 2),
                                        :]
            return [(2 * j + half, jax.lax.bitcast_convert_type(
                x << 16 if half == 0 else x & jnp.uint32(0xFFFF0000),
                jnp.float32)) for half in range(2)]
    else:
        turns, held = hkv, PAGED_ROW_TILE

        def rows_of(buf, slot, r):
            return [(r, buf.at[slot][pl.ds(r, toks, stride=hkv), :])]

    def each_kv_row(buf, slot, do):
        """``do(r, rows)`` for every kv row: a loop, a tile of rows a turn."""
        def turn(t, carry):
            for u in range(held):
                for r, rows in rows_of(buf, slot, t * held + u):
                    do(r, rows)
            return carry
        jax.lax.fori_loop(0, turns // held, turn, 0)

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    tok = jax.lax.broadcasted_iota(jnp.int32, (1, toks), 1)

    def block(i, carry):
        slot = slot_of(i)       # and the stream's next block is started
        kpos = (first_live + i * pages) * bs + tok
        valid = jnp.logical_and(
            kpos < pos, jnp.logical_or(w == 0, pos - kpos < w))
        if selected:
            # no window with a selection: block i starts at page i x pages
            valid = jnp.logical_and(valid, sel_ref[0, pl.ds(i, 1), :] > 0)
        wait(i, slot)

        def scores(r, k):
            s_ref[r] = _split_dot(q_ref[0, r], k, _NT, exact)
        each_kv_row(kbuf, slot, scores)
        s = s_ref[...] * s_scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        s_ref[...] = jnp.exp(s - m_cur)     # p: fp32, never rounded to bf16
        l_ref[...] = l_ref[...] * alpha + jnp.sum(s_ref[...], axis=-1,
                                                  keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha

        def sums(r, v):
            acc_ref[r] += _split_dot(s_ref[r], v, _NN, exact)
        each_kv_row(vbuf, slot, sums)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    # the active token joins in registers: its score the softmax, its V the
    # accumulator (the pools' V is stored / kv_scale, the active V is not)
    m_prev, l_prev, acc = m_ref[...], l_ref[...], acc_ref[...]
    s = jnp.sum(q_ref[0].astype(jnp.float32) * nk_ref[0].astype(jnp.float32),
                axis=-1, keepdims=True) * scale
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    if selected:
        s = jnp.where(sc_ref[2 + nb + nb * mb + b] > 0, s, NEG_INF)
    m_cur = jnp.maximum(m_prev, s)
    if has_sink:
        # learned per-head sink joins the denominator only
        sk = sink_ref[...]
        m_cur = jnp.maximum(m_cur, sk)
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_new = l_prev * alpha + p
    if has_sink:
        l_new = l_new + jnp.exp(sk - m_cur)
    if kv_scale is not None:
        acc = acc * kv_scale
    o_ref[0] = ((acc * alpha + p * nv_ref[0].astype(jnp.float32))
                / l_new).astype(o_ref.dtype)


def _paged_rows_call(scalars, q, new_k, new_v, sink_in, select, k_pages,
                     v_pages, *, pages: int, hkv: int, mb: int, scale: float,
                     soft_cap, has_sink: bool, kv_scale, interpret: bool):
    """:func:`paged_decode_attention`'s call where the plan's form is
    :data:`PAGED_ROWS_FORM`: the same operands (scalars staged, the active K
    / V a row a query head, the pools as ``(L, N, bs x hkv, d)``), the query
    rows grouped by kv row, still ONE Pallas call a layer under the jitted
    function's name."""
    b, hq, d = q.shape
    page_rows = k_pages.shape[2]
    bs, g = page_rows // hkv, hq // hkv
    by_row = pl.BlockSpec((1, hkv, g, d), lambda bi, sc: (bi, 0, 0, 0))
    sel_in, sel_spec = [], []
    if select is not None:
        # whole blocks of tokens, as the kernel counts them
        n_blk = -(-mb // pages)
        sel_in = [jnp.pad(select, ((0, 0), (0, n_blk * pages * bs - mb * bs))
                          ).astype(jnp.float32).reshape(b, n_blk, pages * bs)]
        sel_spec = [pl.BlockSpec((1, n_blk, pages * bs),
                                 lambda bi, sc: (bi, 0, 0))]
    slot = (2, pages * page_rows, d)
    kernel = functools.partial(
        _paged_rows_kernel, scale=scale, bs=bs, mb=mb, pages=pages,
        soft_cap=soft_cap, has_sink=has_sink, kv_scale=kv_scale,
        selected=select is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                by_row, by_row, by_row,
                pl.BlockSpec((hkv, g, 1), lambda bi, sc: (0, 0, 0)),
                *sel_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=by_row,
            scratch_shapes=[
                pltpu.VMEM(slot, k_pages.dtype),
                pltpu.VMEM(slot, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, d), jnp.float32),
                pltpu.VMEM((hkv, g, pages * bs), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=_ROWS_IN_TURN,
        interpret=interpret,
        name="paged_decode_attention",
    )(scalars, *(x.reshape(b, hkv, g, d) for x in (q, new_k, new_v)),
      sink_in.reshape(hkv, g, 1), *sel_in, k_pages, v_pages)
    return out.reshape(b, hq, d)
