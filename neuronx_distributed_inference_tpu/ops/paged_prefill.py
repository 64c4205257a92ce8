"""Pallas paged PREFILL attention over the K / V pools: a chunk of T > 1
queries a row against the row's LIVE pages (a window layer: its ring), scores
in VMEM.

The XLA form (``models/model_base.py`` ``_attn_block``'s ``gathered_mha``)
gathers the WHOLE block table whatever the prefix and holds float32 scores of
rows x heads x width x table tokens: 440 MB a global layer and 126 MB a
window layer of SmallThinker's one-row chunk at any prefix, through HBM
several times. What the model needs is 4 x query rows x lanes FLOP a cached
token a layer (0.019 us at the MXU's peak for 28 heads x 256 queries of 128
lanes) and the token's K and V once.

The pattern is the decode kernel's (``ops/decode_attention.py``
``_paged_kernel``) with the latent prefill kernel's query tiles
(``ops/mla_prefill.py``): layer, window, each row's first position and the
whole block table ride in SMEM; the pools stay in HBM and a block of up to
:data:`PAGED_PREFILL_BLOCK_TOKENS` tokens is copied by hand, one async copy a
page for K and one for V, into one of two VMEM slots while the other is
computed on. The caller has written the chunk's own K / V to the pool already:
the kernel attends positions ``<= own`` causally (inside the window where one
is set) from the first page the chunk's FIRST query may see to the page of
its LAST token, and there is no second softmax to merge. Every block is masked
by position and a slot past the chunk's last page takes that page again, so
the body has one loop over blocks and no branch a page.

The grid is (rows, tiles). A tile is whole kv rows of the page AS STORED
(``block_kv_cache.pool_page``) x their query heads x the row's T queries, at
most :data:`PAGED_PREFILL_TILE_ROWS` query rows. A head's K is read out of the
slot as it lies: a kv row of several heads of whole vregs (4 heads of 128:
SmallThinker; 2 of 256: Qwen3-Next) is cut by lanes, a page of several kv
rows a token (16 heads of 128: OLMoE) by a strided read of every
``rows``-th sublane row, and heads narrower than a vreg (two of 64 to a
128-lane row: granite) score with their queries placed in their own lanes and
zeros in the neighbour's, as the decode kernel's do. A head's queries are its
group's ``g x T`` rows of ONE matmul against the block; float32 scores,
maximum, exponentials, sums and accumulator in VMEM, bf16 operands into the
MXU (a float32 pool: float32 at HIGHEST), the probabilities rounded to the
pool's dtype as ``attention.mha`` rounds them; the result leaves in the
caller's ``(B, T, Hq, D)`` layout. The rows are the grid: a full-batch pack
needs no row groups.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode
from .decode_attention import (NEG_INF, PAGED_BLOCK_PAGES,
                               PAGED_TABLE_SMEM_BYTES, _NN, _NT)

#: most query rows (query heads x queries) of one tile: its queries, float32
#: accumulator, maxima and sums stay in VMEM for the whole walk (40 heads x
#: 256 queries over ONE kv row a token: ten 128-lane heads that share a row
#: are one tile, ~21 MB of the call's 64)
PAGED_PREFILL_TILE_ROWS = 10240
#: most tokens of one compute block (a head's float32 scores against it are
#: ``g x T x 512 x 4`` bytes: 3.7 MB at SmallThinker's 7 x 256 query rows)
PAGED_PREFILL_BLOCK_TOKENS = 512
#: VMEM the two K and two V slots may take together
PAGED_PREFILL_KV_VMEM_BYTES = 8 * 1024 * 1024
#: VMEM a call may use: the tile's queries and result a head after another,
#: accumulator, maxima and sums, the slots, a head's scores and exponentials
PAGED_PREFILL_VMEM_BYTES = 64 * 1024 * 1024


class PrefillPlan(NamedTuple):
    """What one call runs with (:func:`prefill_plan`)."""
    pages: int      # pages a compute block gathers
    fold: int       # kv heads sharing one row of a page, as stored
    rows: int       # kv rows a token in a page
    groups: int     # lane groups a kv row: heads of whole vregs, cut by lanes
    lanes: int      # lanes of a group (a head's, or a vreg of narrow heads)
    g: int          # query heads a group
    tile: int       # kv rows a tile (0: one row's query rows outgrow a tile)

    def note(self, b: int, t: int, window: str) -> str:
        """The engagement record's text (``kernel_mode.note``)."""
        return (f"rows={b} width={t} pages={self.pages} "
                f"heads={self.rows * self.groups * self.g} fold={self.fold} "
                f"tile={self.tile * self.groups * self.g}x{t}{window}")


def prefill_plan(hq: int, d: int, pool, t: int, mb: int) -> PrefillPlan:
    """How a chunk of ``t`` queries a row of ``hq`` heads of ``d`` lanes
    walks ``pool`` (L, N, bs, kv rows, lanes of a row: as stored), chosen
    from what the call shows and from nothing else."""
    _, _, bs, rows, dk = pool.shape
    fold = dk // d
    lanes = max(d, 128) if dk % max(d, 128) == 0 else dk
    groups = dk // lanes
    g = hq // (rows * groups)
    page_bytes = bs * rows * dk * jnp.dtype(pool.dtype).itemsize
    pages = max(1, min(PAGED_PREFILL_KV_VMEM_BYTES // (4 * page_bytes),
                       PAGED_PREFILL_BLOCK_TOKENS // bs, PAGED_BLOCK_PAGES,
                       mb))
    fit = PAGED_PREFILL_TILE_ROWS // (groups * g * t)
    tile = max((n for n in range(1, min(fit, rows) + 1) if rows % n == 0),
               default=0)
    return PrefillPlan(pages, fold, rows, groups, lanes, g, tile)


def declined(spec, q, pool, block_table) -> str:
    """Why a chunk ``q`` (B, T, Hq, D: the queries as the call carries them)
    of ``spec`` over ``pool`` (K's; V's is its twin) does not take the kernel
    ("" = it does), read from what the call shows: the score's extras, the
    pool's dtype and page, the ambient mesh, the table against SMEM, the
    width against sublanes and the tile.

    NO rule on heads, width or table, by the clock. The kernel alone
    against the gathered form (``scripts/paged_prefill_time.py``, one v5e,
    one row of 256 queries, one layer's call, bf16, ms a call at prefixes
    0 / 2048 / 8192 / 14336 where the table holds them, eight calls a
    dispatch; my chip runs, PR 49):

    ===============  =============================  =============  =====
    heads, page      kernel                         gathered       us
    ===============  =============================  =============  =====
    28/4x128 global  0.047 / 0.148 / 0.452 / 0.756  1.88 flat      0.049
    28/4x128 window  0.055 / 0.156 / 0.258 / 0.257  0.544 flat     0.050
    16x128, 16 rows  0.040 / 0.124                  0.086 / 0.084  0.041
    32/8x64, fold 2  0.057 / 0.157                  0.589 flat     0.049
    32x128, 32 rows  0.060                          0.095          -
    16/2x256         0.042 / 0.093                  0.124 flat     0.025
    ===============  =============================  =============  =====

    (us: a cached token, the slope from prefix 0; the floors at the MXU's
    peak are 0.019 us for SmallThinker's 28 x 256 query rows of 128 lanes,
    0.011 for 16 heads, 0.021 for 16 heads of 256 lanes.) The gathered form
    costs the TABLE whatever the prefix; the kernel walks a cached token at
    38 % of the MXU's peak at SmallThinker's shape: with 128-lane heads a
    score element costs 512 FLOP and about six vector operations (scale,
    mask, maximum, subtract, sum, round), so the softmax's vector work
    binds, not the matmuls. OLMoE's 16 kv rows a token pay the float32
    copy of each block on top (Mosaic reads every n-th sublane row of
    32-bit data only) and alone the gathered form of its 4,096-token table
    is AHEAD past ~1.2k cached tokens; inside the chunk program it is not:
    ``olmoe-longprompt-closed`` read ``step.prefill_attn_ms`` 1.79 -> 1.32,
    ``itl_p95_ms`` 59.1-59.3 -> 55.8-55.9 and 811-816 -> 838-840 tokens/s
    with the kernel engaged (mean live prefix ~700), as PR 48 found for the
    latent kernel at 64 heads, so no rule was written. SmallThinker:
    ``step.prefill_attn_ms`` 8.36 -> 2.58, ``itl_p50_ms`` 27.0 -> 21.3."""
    if spec.decode_kernel is False:
        return "decode_kernel=False"
    if spec.alibi or spec.attn_sink:
        return "alibi / sink"
    if spec.attn_chunk:
        return "chunked attention"
    if pool.dtype not in (jnp.bfloat16, jnp.float32):
        return f"pool stored as {pool.dtype}"
    if spec.kv_scale not in (None, 1.0):
        return "scaled KV quantization"
    d, dk = spec.head_dim, pool.shape[4]
    if dk % 128 or dk % d or (d % 128 and 128 % d):
        return f"a kv row of {dk} lanes of heads of {d} is not whole vregs"
    mesh = jax.sharding.get_abstract_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    if wide:
        return "mesh axes wider than one: " + ",".join(wide)
    b, mb = block_table.shape
    if 4 * (2 + b + b * mb) > PAGED_TABLE_SMEM_BYTES:
        return "block table over the SMEM a core can stage"
    _, width, hq, _ = q.shape
    if width % (32 // jnp.dtype(q.dtype).itemsize):
        return f"{width} queries a row are not whole sublanes"
    if not prefill_plan(hq, d, pool, width, mb).tile:
        return (f"{width} queries a row over the kernel's tile of "
                f"{PAGED_PREFILL_TILE_ROWS} query rows")
    return ""


def _kernel(sc_ref, q_hbm, k_hbm, v_hbm, *rest, scale: float,
            bs: int, mb: int, plan: PrefillPlan,
            soft_cap: Optional[float], selected: bool = False):
    """One grid step is one tile of one ROW: ``plan.tile`` kv rows x their
    query heads x the row's T queries. Scalar prefetch: [layer, window,
    first_0..first_{B-1}, table_{0,0}.., table_{B-1,mb-1}]. ``k_hbm`` /
    ``v_hbm`` (L, N, bs x rows, lanes of a row) stay in HBM; ``kbuf`` /
    ``vbuf`` (2, pages x bs x rows, lanes of a row) are the two slots, a
    token's kv rows neighbours as they are stored. q_hbm and o_hbm (B, T,
    query heads x lanes) are the caller's own layouts, left in HBM: the
    tile's queries come into ``q_scr`` a head after another (a group's ``g
    x T`` rows are one matmul's moving operand) and its result leaves
    ``o_scr`` the same way, one strided copy a head in a loop (as loads and
    stores unrolled over 28 heads they were nearly half of the kernel's
    trace and lowering, which every chunk program of a cell's set-up pays;
    the queries' copies run under the first block's); ``bias_ref`` (T,
    block tokens) is the block's mask as 0 / NEG_INF, made once a block for
    all heads; ``wide`` (a 16-bit pool of several kv rows a token) a block's
    K and V as float32: Mosaic reads every ``rows``-th sublane row of 32-bit
    data only.

    ``selected`` (a learned sparse selection, ``model_base.SparseSpec``; no
    window with it): one more input behind the pools, ``sel_hbm`` (B, T,
    blocks x block tokens), > 0 where a query attends that position, and
    its two slots ``sel_buf`` in front of ``wide``; a block's (T, block
    tokens) window of it is copied in beside the block's pages and joins the
    block's mask."""
    if selected:
        (sel_hbm, o_hbm, kbuf, vbuf, sem, q_scr, o_scr, m_ref, l_ref,
         acc_ref, bias_ref, sel_buf, *wide) = rest
    else:
        (o_hbm, kbuf, vbuf, sem, q_scr, o_scr, m_ref, l_ref, acc_ref,
         bias_ref, *wide) = rest
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer, w, first = sc_ref[0], sc_ref[1], sc_ref[2 + b]
    pages, _, rows, groups, dl, g, tile = plan
    t = q_hbm.shape[1]
    heads = tile * groups * g
    head0 = pl.program_id(1) * heads
    gt = g * t
    page_rows = bs * rows
    cols = pages * bs
    # from the first page the chunk's first query may see to the page of its
    # last token (a pad query's position may run past the table: its result
    # is dropped, its reads stay in the table)
    first_page = jax.lax.div(
        jnp.where(w > 0, jnp.maximum(first - w + 1, 0), 0), bs)
    last_page = jnp.minimum(jax.lax.div(first + t - 1, bs), mb - 1)
    n_blocks = jax.lax.div(last_page - first_page, pages) + 1
    table0 = 2 + nb + b * mb
    bf16 = kbuf.dtype == jnp.bfloat16

    def dot(x, y, dims):
        if bf16:
            return jax.lax.dot_general(x.astype(jnp.bfloat16),
                                       y.astype(jnp.bfloat16), dims,
                                       preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            x.astype(jnp.float32), y, dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def each_page(i, slot, do):
        # a slot past the last page takes that page again: every lane the
        # block computes on is a real, finite row and the position mask
        # zeroes its weight. A loop, not an unrolled list (a call's trace is
        # paid in every chunk program of a cell's set-up)
        def page(p, carry):
            at = sc_ref[table0 + jnp.minimum(first_page + i * pages + p,
                                             last_page)]
            into = pl.ds(pl.multiple_of(p * page_rows, page_rows), page_rows)
            do(pltpu.make_async_copy(k_hbm.at[layer, at],
                                     kbuf.at[slot, into], sem.at[0, slot]))
            do(pltpu.make_async_copy(v_hbm.at[layer, at],
                                     vbuf.at[slot, into], sem.at[1, slot]))
            return carry
        jax.lax.fori_loop(0, pages, page, 0)

    def sel_copy(i, slot):
        at = pl.ds(pl.multiple_of(i * (pages * bs), pages * bs), pages * bs)
        return pltpu.make_async_copy(sel_hbm.at[b, :, at], sel_buf.at[slot],
                                     sem.at[3, slot])

    def start(i, slot):
        each_page(i, slot, lambda copy: copy.start())
        if selected:
            sel_copy(i, slot).start()

    def wait(i, slot):
        each_page(i, slot, lambda copy: copy.wait())

    def each_head(do, scr, hbm, to_hbm):
        def head(j, carry):
            mine = scr.at[pl.ds(pl.multiple_of(j * t, t), t)]
            theirs = hbm.at[b, :, pl.ds(pl.multiple_of((head0 + j) * dl, dl),
                                        dl)]
            do(pltpu.make_async_copy(*((mine, theirs) if to_hbm
                                       else (theirs, mine)), sem.at[2, 0]))
            return carry
        jax.lax.fori_loop(0, heads, head, 0)

    start(0, 0)
    each_head(lambda copy: copy.start(), q_scr, q_hbm, False)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    qpos = first + jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
    tok = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row0 = pl.program_id(1) * tile
    each_head(lambda copy: copy.wait(), q_scr, q_hbm, False)

    def block(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _next():
            start(i + 1, 1 - slot)

        kpos = (first_page + i * pages) * bs + tok
        seen = jnp.logical_and(
            kpos <= qpos, jnp.logical_or(w == 0, qpos - kpos < w))
        if selected:
            sel_copy(i, slot).wait()
            seen = jnp.logical_and(
                seen, sel_buf[slot].astype(jnp.float32) > 0)
        bias_ref[...] = jnp.where(seen, 0.0, NEG_INF)
        wait(i, slot)
        k_src, v_src = kbuf.at[slot], vbuf.at[slot]
        if wide:
            k_src, v_src = wide
            k_src[...] = kbuf[slot].astype(jnp.float32)
            v_src[...] = vbuf[slot].astype(jnp.float32)

        def kv_row(r, carry):
            for j in range(groups):
                at = slice(j * dl, (j + 1) * dl)
                mine = (slice(None) if rows == 1
                        else pl.ds(row0 + r, cols, stride=rows))
                k, v = k_src[mine, at], v_src[mine, at]
                own = pl.ds(pl.multiple_of((r * groups + j) * gt, gt), gt)
                s = dot(q_scr[own, :], k, _NT) * scale
                if soft_cap is not None:
                    s = soft_cap * jnp.tanh(s / soft_cap)
                s = (s.reshape(g, t, cols) + bias_ref[...]).reshape(gt, cols)
                m_prev = m_ref[own, :]
                m_cur = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_cur)
                p = jnp.exp(s - m_cur)
                l_ref[own, :] = l_ref[own, :] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_ref[own, :] = acc_ref[own, :] * alpha + dot(p, v, _NN)
                m_ref[own, :] = m_cur
            return carry

        jax.lax.fori_loop(0, tile, kv_row, 0)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    o_scr[...] = (acc_ref[...] * (1.0 / l_ref[...])).astype(o_scr.dtype)
    each_head(lambda copy: copy.start(), o_scr, o_hbm, True)
    each_head(lambda copy: copy.wait(), o_scr, o_hbm, True)


@functools.partial(jax.jit,
                   static_argnames=("scale", "soft_cap", "interpret"))
def paged_prefill_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray, layer, first: jnp.ndarray,
                            block_table: jnp.ndarray, *, scale: float,
                            window=None, soft_cap: Optional[float] = None,
                            select: Optional[jnp.ndarray] = None,
                            interpret: bool = False) -> jnp.ndarray:
    """Attention of a chunk of T queries a row, causal by position (and
    inside ``window`` where it is not 0), over the row's pages of the pools,
    the chunk's own K / V among them (the caller writes them first).

    q (B, T, Hq, D), query ``t`` of a row at position ``first + t``;
    k_pages / v_pages (L, N, Bs, kv rows, lanes of a row) as the application
    stores them (``block_kv_cache.pool_page``: Hkv heads of D a token, a few
    to a row); first (B,) each row's first position; block_table (B,
    max_blocks), entry ``j`` the page of positions ``[j x Bs, (j + 1) x
    Bs)`` (a window layer's: its ring as LOGICAL pages,
    ``window_ring_inputs``'s ``kernel_table``); window a scalar, traced or
    not; select (B, T, max_blocks x Bs) bool, optional (a learned sparse
    selection, and then no window): the positions each query attends - every
    other cached token is walked and left out. Returns (B, T, Hq, D)."""
    b, t, hq, d = q.shape
    n_layers, n, bs, rows, dk = k_pages.shape
    mb = block_table.shape[1]
    plan = prefill_plan(hq, d, k_pages, t, mb)
    pages, fold, _, groups, dl, g, tile = plan
    placed = dl != d
    if placed:
        # heads narrower than a vreg: a query row holds its own head's lanes
        # and zeros in its neighbours', and its result is read back from them
        own = (np.arange(hq) // (g // fold) % fold)[:, None] \
            == np.arange(fold)
        q = jnp.where(own[None, None, :, :, None], q[:, :, :, None, :],
                      jnp.zeros((), q.dtype))
    heads = tile * groups * g
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(0 if window is None else window, jnp.int32).reshape(1),
        first.astype(jnp.int32), block_table.astype(jnp.int32).reshape(-1)])
    kernel = functools.partial(_kernel, scale=scale, bs=bs, mb=mb, plan=plan,
                               soft_cap=soft_cap, selected=select is not None)
    sel_in, sel_scr = [], []
    if select is not None:
        # whole blocks of columns, in the queries' dtype (a 16-bit tile is
        # (16, 128): T is whole sublanes of it, ``declined``)
        cols = pages * bs
        sel_in = [jnp.pad(select, ((0, 0), (0, 0),
                                   (0, -(-mb // pages) * cols - mb * bs))
                          ).astype(q.dtype)]
        sel_scr = [pltpu.VMEM((2, t, cols), q.dtype)]
    slot = (2, pages * bs * rows, dk)
    # a 16-bit pool of several kv rows a token: a block as float32 beside it
    wide = [pltpu.VMEM(slot[1:], jnp.float32)] * 2 if (
        rows > 1 and jnp.dtype(k_pages.dtype).itemsize < 4) else []
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, rows // tile),
            in_specs=[in_hbm, in_hbm, in_hbm] + [in_hbm] * len(sel_in),
            out_specs=in_hbm,
            scratch_shapes=[
                pltpu.VMEM(slot, k_pages.dtype),
                pltpu.VMEM(slot, v_pages.dtype),
                pltpu.SemaphoreType.DMA((4, 2)),
                pltpu.VMEM((heads * t, dl), q.dtype),
                pltpu.VMEM((heads * t, dl), q.dtype),
                pltpu.VMEM((heads * t, 1), jnp.float32),
                pltpu.VMEM((heads * t, 1), jnp.float32),
                pltpu.VMEM((heads * t, dl), jnp.float32),
                pltpu.VMEM((t, pages * bs), jnp.float32), *sel_scr, *wide,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, hq * dl), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=PAGED_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="paged_prefill_attention",
    )(scalars, q.reshape(b, t, hq * dl),
      k_pages.reshape(n_layers, n, bs * rows, dk),
      v_pages.reshape(n_layers, n, bs * rows, dk), *sel_in)
    if placed:
        out = jnp.sum(jnp.where(own[None, None, :, :, None],
                                out.reshape(b, t, hq, fold, d),
                                jnp.zeros((), out.dtype)), axis=3)
    return out.reshape(b, t, hq, d)


def chunk_attention(spec, q, k_pages, v_pages, layer, positions, block_table,
                    window, window_note: str = "", select=None):
    """The call site's whole decision (``model_base._attn_block``, phase
    "paged", a chunk of T > 1): the kernel's result (B, T, Hq, D) and its
    plan in the engagement record, or None and why it was declined there
    (the caller then takes ``gathered_mha``). ``window``: the layer's window
    as a scalar, traced under a ``layer_pattern``; ``window_note`` the
    record's words for it; ``select`` (B, T, table tokens) bool, a learned
    sparse selection: the positions each query attends, which the kernel
    takes as one more mask (the engagement record ``sparse_attn`` says which
    form the chunk took)."""
    b, t, hq, d = q.shape
    why = declined(spec, q, k_pages, block_table)
    if select is not None:
        kernel_mode.note(
            "sparse_attn", "xla" if why else kernel_mode.kernel_path(),
            f"masked: rows={b} width={t}, a query attends where selected"
            + (f" ({why}: the table gathered)" if why else ""))
    if why:
        kernel_mode.note("paged_prefill", "xla",
                         f"rows={b} width={t}: {why}")
        return None
    kernel_mode.note(
        "paged_prefill", kernel_mode.kernel_path(),
        prefill_plan(hq, d, k_pages, t, block_table.shape[1]).note(
            b, t, window_note))
    return paged_prefill_attention(
        q, k_pages, v_pages, layer, positions[:, 0], block_table,
        scale=spec.scale, window=window, soft_cap=spec.attn_soft_cap,
        select=select, interpret=kernel_mode.pallas_interpret())
