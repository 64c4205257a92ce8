"""Pallas selection of a learned sparse attention: a step's or a chunk's index
queries score the row's LIVE index pages and pick their top-k, scores in VMEM.

The XLA form (``models/model_base.py`` ``_indexer_block``:
``gather_index_rows`` -> ``_index_scores`` -> ``topk_select`` under
``map_row_groups``) gathers the WHOLE block table whatever the row holds and
sends its float32 scores through HBM nine times: once to make them and eight
counting passes, four bits of the k-th largest a pass. What the model needs is
a row's live index keys once and a few vector operations a score.

The pattern is the paged prefill kernel's (``ops/paged_prefill.py``): layer,
each band's last position and the whole block table ride in SMEM; the pool
stays in HBM and a block of :data:`INDEX_BLOCK_PAGES` pages is copied by hand,
one async copy a live page, into one of two VMEM slots while the other is
scored. The caller has written the step's own keys to the pool already. No
entry of the table past the tile's last live page is read.

The grid is (rows, tiles of queries). A tile:

* **scores** a block of pages at a time. A page is read as it lies
  (``block_kv_cache.index_page``: ``fold`` tokens to a 128-lane row); the
  block's keys are laid out token-major with a segment's lanes kept and the
  neighbours' zeroed, the queries repeated in every segment, so ONE matmul a
  head gives the block's columns in position order (the products and their
  float32 sums are ``_index_scores``'s: the zero lanes add exact zeros). ReLU,
  the head weights and the sum over heads in float32; the causal mask by
  position; the scores are kept as their ORDER KEYS (``_float_order_keys``
  shifted to int32: the same order, an unseen column the least) in a (queries,
  table tokens) VMEM block that never leaves the chip.
* **selects** in bands of :data:`INDEX_BAND_ROWS` queries over the band's LIVE
  columns only: the k-th largest key a query one BIT a pass (32 counting
  passes of a compare and an add over VMEM, where four bits a pass cost 15
  compares), then what lies above it and, of the keys AT it, as many as there
  is room for from the lowest position (the room-th tie's position found the
  same way, taken only where some query of the band has more ties than room:
  float32 scores of real activations tie at exact zeros alone). A band that
  sees at most ``k`` tokens takes them all without a search, and a tile whose
  last query does without scores too. ``topk_select``'s set, exactly, wherever
  the float32 scores are equal bit for bit.

The result leaves as ``select`` (B, T, table tokens) bool, what both paged
attention kernels take as their mask.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode
from .decode_attention import PAGED_TABLE_SMEM_BYTES, _NT

#: pages of one compute block (512 tokens of the served page)
INDEX_BLOCK_PAGES = 16
#: queries that search together: their counters stay in vregs
INDEX_BAND_ROWS = 32
#: most queries of one tile
INDEX_TILE_ROWS = 256
#: VMEM a tile's order keys may take (queries x table tokens x 4 bytes)
INDEX_KEYS_VMEM_BYTES = 24 * 1024 * 1024
#: VMEM a call may use
INDEX_SELECT_VMEM_BYTES = 64 * 1024 * 1024

_LEAST = -2 ** 31          # an unseen column's key: below every float's


class SelectPlan(NamedTuple):
    """What one call runs with (:func:`select_plan`)."""
    pages: int      # pages a compute block copies
    fold: int       # tokens sharing one row of a page, as stored
    tile: int       # queries a tile (0: no tile fits)
    band: int       # queries a band

    def note(self, b: int, t: int, heads: int, dim: int, topk: int) -> str:
        """The engagement record's text (``kernel_mode.note``)."""
        return (f"rows={b} width={t} pages={self.pages} heads={heads}x{dim} "
                f"fold={self.fold} topk={topk} tile={self.tile}x{self.pages}")


def select_plan(dim: int, pool, t: int, mb: int) -> SelectPlan:
    """How ``t`` queries a row of index heads of ``dim`` lanes walk ``pool``
    (L, N, page rows, lanes: as stored) under a table of ``mb`` pages, chosen
    from what the call shows and from nothing else."""
    _, _, prow, lanes = pool.shape
    fold = lanes // dim
    pages = min(INDEX_BLOCK_PAGES, mb)
    tokens = -(-mb // pages) * pages * prow * fold
    fit = min(INDEX_TILE_ROWS, INDEX_KEYS_VMEM_BYTES // (4 * tokens))
    tile = 1 if t == 1 else max(
        (n for n in range(8, min(fit, t) + 1, 8) if t % n == 0), default=0)
    band = next((n for n in (INDEX_BAND_ROWS, 16, 8, 1) if tile % n == 0), 1)
    return SelectPlan(pages, fold, tile, band)


def declined(spec, qi, pool, block_table) -> str:
    """Why the index queries ``qi`` (B, T, heads, index_dim) of ``spec`` over
    the index-key ``pool`` do not take the kernel ("" = they do), read from
    what the call shows: the switch, the pool's dtype and page, the ambient
    mesh, the table against SMEM and VMEM, the width.

    ONE rule by the clock: a single query a row (a decode step) keeps the
    gathered form. ``scripts/index_select_time.py`` at
    ``keye-vl2-videoqa-closed``'s shape (16 heads of 64, ``topk`` 2048, a
    table of 12,288 tokens, bf16, one v5e; my chip runs, PR 51), ms behind
    prefixes 0 / 2048 / 6144 / 10240; alone: one layer's call, eight calls a
    dispatch; program: the cell's whole ``paged.w1`` (32 rows) or
    ``paged.w256`` (one row), twelve layers, either form inside:

    ===============  ========  ======  ======  ======  ======
    where            form      0       2048    6144    10240
    ===============  ========  ======  ======  ======  ======
    256, alone       kernel    0.031   0.100   0.186   0.271
    256, alone       gathered  0.456   0.457   0.455   0.455
    256, program     kernel    5.24    7.44    11.51   15.71
    256, program     gathered  10.89   12.23   15.96   18.72
    32 x 1, alone    kernel    0.066   0.287   0.575   0.866
    32 x 1, alone    gathered  0.112   0.112   0.112   0.112
    32 x 1, program  kernel    4.84    10.75   18.79   26.74
    32 x 1, program  gathered  7.71    10.40   15.06   19.68
    ===============  ========  ======  ======  ======  ======

    A chunk's selection follows the live length (0.1 ms a layer at the mean
    prefix of the cell's traffic, a fifth of the gathered form's) and is
    ahead at every prefix. A single query is one sublane of a vreg's eight:
    its 32 passes run at an eighth of the VPU's density and each ends in a
    cross-lane sum the next one waits for, row after row, so 32 rows pay
    ~18 us each at 6k tokens where XLA's table-wide passes over all rows at
    once cost 0.11 ms flat; the step program is 3.7 ms SLOWER with the kernel
    at 6k tokens a row. The kernel runs a single query all the same (the
    tests hold it to the oracle there)."""
    if spec.decode_kernel is False:
        return "decode_kernel=False"
    if pool.dtype not in (jnp.bfloat16, jnp.float32):
        return f"pool stored as {pool.dtype}"
    dim, lanes = qi.shape[3], pool.shape[3]
    if lanes % 128 or lanes % dim:
        return (f"an index page row of {lanes} lanes of keys of {dim} is not "
                "whole vregs")
    mesh = jax.sharding.get_abstract_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    if wide:
        return "mesh axes wider than one: " + ",".join(wide)
    b, mb = block_table.shape
    width = qi.shape[1]
    if 4 * (1 + b * width + b * mb) > PAGED_TABLE_SMEM_BYTES:
        return "block table over the SMEM a core can stage"
    if width == 1:
        return "one query a row: the gathered form is ahead by the clock"
    if width % 8:
        return f"{width} queries a row are not whole sublanes"
    if not select_plan(dim, pool, width, mb).tile:
        return (f"the order keys of a table of {mb} pages over the kernel's "
                f"{INDEX_KEYS_VMEM_BYTES} bytes of VMEM")
    return ""


def _order_keys(x):
    """float32 -> int32 whose signed order is the floats' (``-0.0`` taken as
    ``+0.0`` first): ``model_base._float_order_keys`` less 2**31, so every
    finite float lies above :data:`_LEAST`."""
    x = jnp.where(x == 0, jnp.zeros((), jnp.float32), x)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _kernel(sc_ref, q_ref, w_ref, pos_ref, pool_hbm, out_ref, kbuf, sem,
            acc_ref, keys_ref, pstar_ref, *, plan: SelectPlan, bs: int,
            mb: int, dim: int, topk: int):
    """One grid step is one tile of one ROW's queries. Scalar prefetch:
    [layer, each band's last position (rows x tiles x bands), the table].
    ``q_ref`` (1, groups, rows, lanes) and ``w_ref`` (1, groups, rows, 1):
    a chunk's tile has a group a head and a row a query, a single query has
    ONE group whose rows are its heads (summed over sublanes at the end);
    ``pos_ref`` (1, tile, 1) each query's position; ``pool_hbm`` (L, N, page
    rows, lanes) stays in HBM; ``kbuf`` (2, pages, page rows, lanes) the two
    slots; ``acc_ref`` a block's scores; ``keys_ref`` (tile, table tokens)
    the order keys; ``pstar_ref`` (band, 1) the last position a band's
    queries take of their ties; ``out_ref`` (1, tile, table tokens), > 0
    where selected."""
    b, ti = pl.program_id(0), pl.program_id(1)
    nt = pl.num_programs(1)
    pages, fold, tile, band = plan
    _, groups, rows, lanes = q_ref.shape
    _, prow, _ = kbuf.shape[1:]
    cols = pages * bs
    n_bands = tile // band
    layer = sc_ref[0]
    band0 = 1 + (b * nt + ti) * n_bands
    table0 = 1 + pl.num_programs(0) * nt * n_bands + b * mb
    exact = q_ref.dtype == jnp.bfloat16
    nbits = (mb * bs).bit_length()

    def last_of(i):
        # a pad query's position may run past the table (its selection is
        # dropped) or be negative (it sees nothing): the walk stays inside
        return jnp.clip(sc_ref[band0 + i], 0, mb * bs - 1)

    tile_last = last_of(0)
    for i in range(1, n_bands):
        tile_last = jnp.maximum(tile_last, last_of(i))
    last_page = jax.lax.div(tile_last, bs)
    n_blocks = jax.lax.div(last_page, pages) + 1
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def each_page(i, slot, do):
        def page(p, carry):
            at = sc_ref[table0 + i * pages + p]
            do(pltpu.make_async_copy(pool_hbm.at[layer, at],
                                     kbuf.at[slot, p], sem.at[slot]))
            return carry
        # the block's live pages alone: what a slot held before is masked
        # by position
        jax.lax.fori_loop(
            0, jnp.minimum(pages, last_page + 1 - i * pages), page, 0)

    def dot(x, y):
        if exact:
            return jax.lax.dot_general(x, y.astype(jnp.bfloat16), _NT,
                                       preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            x.astype(jnp.float32), y.astype(jnp.float32), _NT,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def at_block(c):
        return pl.ds(pl.multiple_of(c * cols, cols), cols)

    @pl.when(tile_last < topk)
    def _all_seen():
        def block(c, carry):
            seen = c * cols + col <= pos_ref[0]
            out_ref[0, :, at_block(c)] = seen.astype(out_ref.dtype)
            return carry
        jax.lax.fori_loop(0, n_blocks, block, 0)

    @pl.when(tile_last >= topk)
    def _search():
        each_page(0, 0, lambda copy: copy.start())
        seg = jax.lax.broadcasted_iota(jnp.int32, (1, 1, lanes), 2) // dim

        def block(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _next():
                each_page(i + 1, 1 - slot, lambda copy: copy.start())

            each_page(i, slot, lambda copy: copy.wait())
            kb = kbuf[slot]                            # (pages, prow, lanes)
            # token-major: token o of a page lies in row o % prow, segment
            # o // prow; its row with the neighbours' lanes zeroed
            k2 = kb if fold == 1 else jnp.stack(
                [jnp.where(seg == g, kb, jnp.zeros((), kb.dtype))
                 for g in range(fold)], axis=1)
            k2 = k2.reshape(cols, lanes)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

            def group(j, carry):
                s = dot(q_ref[0, j], k2)
                acc_ref[...] += jnp.maximum(s, 0.0) * w_ref[0, j]
                return carry

            jax.lax.fori_loop(0, groups, group, 0)
            scores = acc_ref[...]
            if rows != tile:                           # one query: its heads
                scores = jnp.sum(scores, axis=0, keepdims=True)
            seen = i * cols + col <= pos_ref[0]
            keys_ref[:, at_block(i)] = jnp.where(
                seen, _order_keys(scores), jnp.int32(_LEAST))
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)

        def select_band(bi, carry):
            last = last_of(bi)
            mine = pl.ds(pl.multiple_of(bi * band, band), band)
            blocks = jax.lax.div(last, cols) + 1

            def count(pred):
                def block(c, n):
                    hit = pred(keys_ref[mine, at_block(c)], c).astype(
                        jnp.int32)
                    for v in range(0, cols, 128):
                        n = n + hit[:, v:v + 128]
                    return n
                n = jax.lax.fori_loop(
                    0, blocks, block,
                    jnp.zeros((band, min(cols, 128)), jnp.int32))
                return jnp.sum(n, axis=1, keepdims=True)

            def write(pred):
                def block(c, carry):
                    out_ref[0, mine, at_block(c)] = pred(
                        keys_ref[mine, at_block(c)], c).astype(out_ref.dtype)
                    return carry
                jax.lax.fori_loop(0, blocks, block, 0)

            @pl.when(last < topk)
            def _all():
                write(lambda key, c: key != _LEAST)

            @pl.when(last >= topk)
            def _kth():
                def one_bit(s, prefix):
                    bit = jnp.left_shift(jnp.int32(1), 31 - s)
                    cand = (prefix | bit) ^ jnp.int32(_LEAST)
                    n = count(lambda key, c: key >= cand)
                    return jnp.where(n >= topk, prefix | bit, prefix)

                tau = jax.lax.fori_loop(
                    0, 32, one_bit, jnp.zeros((band, 1), jnp.int32)
                ) ^ jnp.int32(_LEAST)
                # a query that sees under k keys has the unseen ones' key
                # for its k-th: nothing lies AT a threshold there
                some = tau != _LEAST
                room = topk - count(lambda key, c: key > tau)
                n_at = count(lambda key, c: (key == tau) & some)
                pstar_ref[...] = jnp.full((band, 1), mb * bs, jnp.int32)

                @pl.when(jnp.max((n_at - room).astype(jnp.float32)) > 0)
                def _crowded():
                    def one_bit(s, q):
                        cand = q | jnp.left_shift(jnp.int32(1), nbits - 1 - s)
                        n = count(lambda key, c: (key == tau)
                                  & (c * cols + col < cand))
                        return jnp.where(n < room, cand, q)

                    q = jax.lax.fori_loop(
                        0, nbits, one_bit, jnp.zeros((band, 1), jnp.int32))
                    pstar_ref[...] = jnp.where(
                        n_at <= room, mb * bs, jnp.where(room > 0, q, -1))

                write(lambda key, c: (key > tau) | (
                    (key == tau) & some & (c * cols + col <= pstar_ref[...])))

            return carry

        jax.lax.fori_loop(0, n_bands, select_band, 0)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def index_select(qi: jnp.ndarray, w: jnp.ndarray, pool: jnp.ndarray, layer,
                 positions: jnp.ndarray, block_table: jnp.ndarray, *,
                 topk: int, interpret: bool = False) -> jnp.ndarray:
    """Which cached tokens each query attends: the ``topk`` of largest index
    score among those at or before its position, ties to the lower position,
    all of them where it sees at most ``topk``.

    qi (B, T, heads, index_dim) the rotated index queries; w (B, T, heads)
    their weights; pool (L, N, page rows, lanes) the index keys as the
    application stores them (``block_kv_cache.index_page``), the step's own
    among them; positions (B, T) each query's position; block_table (B,
    max_blocks), entry ``j`` the page of positions ``[j x Bs, (j + 1) x
    Bs)``. Returns (B, T, max_blocks x Bs) bool."""
    b, t, heads, dim = qi.shape
    _, _, prow, lanes = pool.shape
    mb = block_table.shape[1]
    plan = select_plan(dim, pool, t, mb)
    pages, fold, tile, band = plan
    bs = prow * fold
    # the queries in every segment's lanes; a chunk head-major, a single
    # query its heads as rows
    q = jnp.tile(qi, (1, 1, 1, fold))
    wf = w.astype(jnp.float32)[..., None]
    if t > 1:
        q, wf = q.transpose(0, 2, 1, 3), wf.transpose(0, 2, 1, 3)
    groups, rows = (heads, tile) if t > 1 else (1, heads)
    positions = positions.astype(jnp.int32)
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.max(positions.reshape(b, t // band, band), axis=2).reshape(-1),
        block_table.astype(jnp.int32).reshape(-1)])
    tokens = -(-mb // pages) * pages * bs         # whole blocks of columns
    # a packed dtype's tile is deeper than a band of a narrow width
    out_dtype = jnp.int8 if band % 32 == 0 else jnp.float32
    kernel = functools.partial(_kernel, plan=plan, bs=bs, mb=mb, dim=dim,
                               topk=topk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // tile),
            in_specs=[
                pl.BlockSpec((1, groups, rows, lanes),
                             lambda bi, ti, sc: (bi, 0, ti, 0)),
                pl.BlockSpec((1, groups, rows, 1),
                             lambda bi, ti, sc: (bi, 0, ti, 0)),
                pl.BlockSpec((1, tile, 1), lambda bi, ti, sc: (bi, ti, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, tile, tokens),
                                   lambda bi, ti, sc: (bi, ti, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, prow, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, pages * bs), jnp.float32),
                pltpu.VMEM((tile, tokens), jnp.int32),
                pltpu.VMEM((band, 1), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, tokens), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=INDEX_SELECT_VMEM_BYTES),
        interpret=interpret,
        name="index_select",
    )(scalars, q, wf, positions[..., None], pool)
    return out[:, :, :mb * bs] != 0


def select_of(spec, qi, w, pool, layer, positions, block_table):
    """The call site's whole decision (``model_base._indexer_block``): the
    kernel's selection and its plan in the engagement record, or None and why
    it was declined there (the caller then takes the gathered form)."""
    sp = spec.sparse
    b, t, heads, dim = qi.shape
    why = declined(spec, qi, pool, block_table)
    if why:
        kernel_mode.note("index_select", "xla", f"rows={b} width={t}: {why}")
        return None
    kernel_mode.note(
        "index_select", kernel_mode.kernel_path(),
        select_plan(dim, pool, t, block_table.shape[1]).note(
            b, t, heads, dim, sp.topk))
    return index_select(qi, w, pool, layer, positions, block_table,
                        topk=sp.topk, interpret=kernel_mode.pallas_interpret())
