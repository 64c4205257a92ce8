"""Pallas paged PREFILL attention over a LATENT pool (Multi-head Latent
Attention: deepseek_v2/v3, longcat_flash), in the absorbed form: a chunk of
T > 1 queries a row against the row's live latent pages, scores in VMEM.

What a token leaves in the pool is one row a layer, ``[c | rot(k_rope) | 0]``
(``ops/mla_decode.py`` has the algebra). With ``q_lat = W_UK^T q_nope`` every
(head, query) pair is ONE row ``[q_lat | q_rot | 0]`` of a multi-query flash
attention against ONE shared key / value stream: ``heads x T`` query rows of
``lanes`` lanes (32,768 at DeepSeek-V3's 128 heads and a 256-token chunk),
the first ``rank`` lanes of a cached row doubling as its value, ``W_UV``
applied after the softmax. A cached token costs 2 x heads x T x (lanes +
rank) FLOP a layer and its 1,280 bytes once a query tile: nothing of ``heads
x T x tokens`` leaves VMEM, where the XLA form (``models/model_base.py``
``_mla_attend``) sends a float32 score tensor of 64 MB through HBM several
times a group of 512 cached tokens.

The pattern is the decode kernel's (PR 33 / PR 40): layer, each row's first
position and the whole block table ride in SMEM; the pool stays in HBM and a
block of up to 512 tokens is copied by hand, one async copy a page, into one
of two VMEM slots while the other is computed on. The grid is (rows, query
tiles); a tile is ``tile_heads`` whole heads x the row's T queries, at most
:data:`MLA_PREFILL_TILE_ROWS` rows. BOTH folds are inside: a tile reads its
heads' ``q_nope`` and writes its heads' outputs in the caller's own (B, T,
heads x lanes) layouts (a head is whole vregs there), holds its heads'
columns of ``kv_b_proj`` in VMEM, folds ``W_UK`` into the queries once a
tile under the first block's copies and applies ``W_UV`` to the normalised
sums before they leave: neither ``q_lat`` nor the latent sums (32 MB each at
128 heads) exist in HBM (outside the kernel they cost 0.18 ms of a 0.70 ms
call at a prefix of 0, and a pack its row groups). The caller has written
the chunk's own latent rows to the pool already: the kernel attends positions
``<= own`` causally and there is no second softmax to merge. Every block is
masked by position (the mask's compare and select hide under the MXU), and a
slot past the chunk's last page takes that page again, so the body has one
loop over blocks, one over a block's pages and no branch a page: a call's
trace and lowering are paid in every chunk program of a cell's set-up.

Arithmetic: bf16 operands into the MXU (a float32 pool: float32 at HIGHEST),
float32 scores, maximum, exponentials, sums and accumulator, ``q_lat`` and
the normalised sums rounded to the queries' dtype as ``_mla_attend``'s
absorbed fork rounds them; the summation order differs.

The form was settled by the clock (:func:`declined` has the table): absorbed,
because it shares one stream among all heads at full MXU tiles and reached
86 % of the MXU's peak as written; the expanded form's floor is a quarter
lower (0.277 against 0.362 us a cached token at 128 heads), which a kernel
that expands a block through ``kv_b_proj`` in VMEM could only collect above
~75 % of peak on contractions of 192 lanes: not tried.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode, mla_decode
from .decode_attention import NEG_INF, _NN, _NT
from .mla_decode import block_pages

#: most query rows (heads x queries) of one tile: its float32 scores against
#: a block of 512 tokens are 4 MiB, its accumulator 4 MiB (a tile of 512 /
#: 1024 / 2048 rows walked a cached token in 0.502 / 0.449 / 0.421 us at 128
#: heads, my chip runs, PR 48: a block's weight tiles are loaded once a tile)
MLA_PREFILL_TILE_ROWS = 2048
#: most heads of one tile: their ``kv_b_proj`` columns ride in VMEM twice
#: (1 MiB at 8 heads of 256 columns) and the two folds are unrolled over them
MLA_PREFILL_TILE_HEADS = 8
#: VMEM a call may use: the folded queries, the accumulator, a block's scores
#: and exponentials, the two slots, the tile's operands twice
MLA_PREFILL_VMEM_BYTES = 40 * 1024 * 1024


def tile_heads(heads: int, width: int) -> int:
    """Whole heads of one query tile: the most that divide ``heads`` and keep
    the tile at :data:`MLA_PREFILL_TILE_ROWS` rows and
    :data:`MLA_PREFILL_TILE_HEADS` heads; 0 where one head's ``width``
    queries are already more."""
    fit = min(MLA_PREFILL_TILE_ROWS // width, MLA_PREFILL_TILE_HEADS, heads)
    return max((d for d in range(1, fit + 1) if heads % d == 0), default=0)


def plan_note(pool: jnp.ndarray, heads: int, rows: int, width: int) -> str:
    """The engagement record's text: what a call over ``pool`` runs with."""
    _, _, bs, _, lanes = pool.shape
    hb = tile_heads(heads, width)
    return (f"rows={rows} width={width} latent lanes={lanes} heads={heads} "
            f"form=absorbed tile={hb}x{width} "
            f"pages={block_pages(bs, lanes, pool.dtype, 1 << 30)} "
            "folds and own tokens inside")


def declined(spec, pool: jnp.ndarray, block_table: jnp.ndarray,
             width: int) -> str:
    """Why a chunk of ``width`` queries a row of ``spec`` over the latent
    ``pool`` does not take the kernel ("" = it does), read from what the call
    shows: what the decode kernel declines (``mla_decode.declined``: the
    pool's dtype and lanes, ``kv_scale``, soft cap / sink / alibi / window,
    the ambient mesh, the table against SMEM), the heads' widths, the width
    against the query tile.

    NO rule on the head count or on a least width, by the clock. The kernel
    alone (``scripts/mla_prefill_time.py``, one v5e, one row, one layer's
    call, bf16, ms at prefixes 0 / 2048 / 7168, wall clock over 20 calls;
    both sides carry ~0.11 ms of a pool relayout that only the stand-alone
    call pays; my chip runs, PR 48):

    ==================  ==================  ==================  ===========
    heads x width       kernel              XLA form            us a token
    ==================  ==================  ==================  ===========
    128 x 256 = 32,768  0.52 / 1.38 / 3.57  0.37 / 1.94 / 5.84  0.4225|0.763
     64 x 256 = 16,384  0.24 / 0.68 / 1.76  0.25 / 0.62 / 1.75  0.213|0.20
    128 x  64 =  8,192  0.25 / 0.49 / 1.11  0.34 / 0.55 / 1.18  0.119|0.11
     64 x  64 =  4,096  0.21 / 0.23 / 0.54  0.24 / 0.28 / 0.63  0.047|0.05
    ==================  ==================  ==================  ===========

    (XLA form: expanded at 256 queries, absorbed at 64, as
    ``MLA_EXPAND_MIN_QUERIES`` forks; the two rows at 64 queries with the
    page copies still unrolled, 2 % faster.) The kernel walks a cached token
    at 86 % of the MXU's peak for the absorbed form's FLOP (0.4225 us against
    0.362 at 128 heads, 0.213 against 0.181 at 64). Alone, XLA's expanded form at 64
    heads (a group's float32 scores are 32 MB) is level with it; at 128
    heads (64 MB) XLA falls to 36 % of its own floor and the kernel walks in
    55 % of its time. INSIDE the chunk program the XLA form costs more than
    alone at both head counts, so the cells decided: LongCat's 64 heads with
    the kernel declined under 32,768 query rows a row read ``itl_p50_ms``
    34.63 and 794 tokens/s against the parent's 34.51 and 780-804, with it
    engaged 32.81 / 32.90 and 851 / 852 against 34.45 / 34.51 and 804 / 799
    (``step.prefill_attn_ms`` 8.87 -> 7.99), so the rule went; DeepSeek-V3's
    128 heads ``itl_p95_ms`` 43.1-44.0 -> 35.4-36.1, 1800-1810 -> 1901-1926
    tokens/s (``step.prefill_attn_ms`` 17.64 -> 8.36-10.26)."""
    why = mla_decode.declined(spec, pool, block_table)
    if why:
        return why
    m = spec.mla
    if m.qk_nope_head_dim % 128 or m.v_head_dim % 128:
        return "a head's nope or value lanes not whole vregs"
    if width % 16:
        return f"{width} queries a row are not whole sublanes"
    if not tile_heads(spec.gqa.num_q_heads, width):
        return (f"{width} queries a row over the kernel's tile of "
                f"{MLA_PREFILL_TILE_ROWS} query rows")
    return ""


def _kernel(sc_ref, qn_ref, qr_ref, w_ref, lat_hbm, o_ref, buf, sem, q_ref,
            m_ref, l_ref, acc_ref, *, scale: float, bs: int, mb: int,
            rank: int, nope: int):
    """One grid step is one query tile of one ROW: ``hb`` whole heads x the
    row's T queries. Scalar prefetch: [layer, first_0..first_{B-1},
    table_{0,0}.., table_{B-1,mb-1}]. ``lat_hbm`` (L, N, bs, lanes) stays in
    HBM; ``buf`` (2, pages, bs, lanes) are the two slots. qn_ref (1, T, hb x
    nope) and o_ref (1, T, hb x v) are the caller's own layouts, a head a
    group of whole vregs; qr_ref (1, hb, T, lanes - rank) the rotary queries
    a head at a time, zero past ``rope``; w_ref (rank, hb x (nope + v)) the
    tile's heads of ``kv_b_proj``. ``q_ref`` (hb x T, lanes) holds the folded
    rows ``[W_UK^T q_nope | q_rot | 0]``, made here once a tile; the sums of
    ``c`` leave through ``W_UV`` here too."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = sc_ref[0]
    first = sc_ref[1 + b]
    _, pages, _, lanes = buf.shape
    cols = pages * bs
    hb, t = qr_ref.shape[1], qr_ref.shape[2]
    per_head = w_ref.shape[1] // hb
    v = per_head - nope
    # the last page any query of the row attends (a pad query's position may
    # run past the table: its result is dropped, its reads stay in the table)
    last_page = jnp.minimum(jax.lax.div(first + t - 1, bs), mb - 1)
    n_blocks = jax.lax.div(last_page, pages) + 1
    table0 = 1 + nb + b * mb
    bf16 = buf.dtype == jnp.bfloat16

    def dot(x, w, dims):
        if bf16:
            return jax.lax.dot_general(x.astype(jnp.bfloat16),
                                       w.astype(jnp.bfloat16), dims,
                                       preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            x.astype(jnp.float32), w.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def each_page(i, slot, do):
        # a slot past the last page takes that page again: every lane the
        # block computes on is a real, finite row, and the position mask
        # zeroes its weight (no branch a page, no blanking). A loop, not an
        # unrolled list: 48 copies traced one by one were over half of the
        # call's trace, which every chunk program of a cell's set-up pays
        def page(p, carry):
            at = sc_ref[table0 + jnp.minimum(i * pages + p, last_page)]
            do(pltpu.make_async_copy(lat_hbm.at[layer, at], buf.at[slot, p],
                                     sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, pages, page, 0)

    def start(i, slot):
        each_page(i, slot, lambda copy: copy.start())

    def wait(i, slot):
        each_page(i, slot, lambda copy: copy.wait())

    start(0, 0)
    # the tile's folded query rows, under the first block's copies
    for j in range(hb):
        q_ref[j * t:(j + 1) * t, :rank] = dot(
            qn_ref[0, :, j * nope:(j + 1) * nope],
            w_ref[:, j * per_head:j * per_head + nope], _NT
        ).astype(q_ref.dtype)
        q_ref[j * t:(j + 1) * t, rank:] = qr_ref[0, j]
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    qpos = first + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (hb * t, 1), 0), t)
    tok = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def block(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _next():
            start(i + 1, 1 - slot)

        wait(i, slot)
        rows = buf[slot].reshape(cols, lanes)
        s = dot(q_ref[...], rows, _NT) * scale
        s = jnp.where(i * cols + tok <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + dot(p, rows[:, :rank], _NN)
        m_ref[...] = m_cur
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    # W_UV on the normalised sums, rounded as the XLA form rounds them
    for j in range(hb):
        rows_j = slice(j * t, (j + 1) * t)
        c_sum = (acc_ref[rows_j] * (1.0 / l_ref[rows_j])).astype(q_ref.dtype)
        o_ref[0, :, j * v:(j + 1) * v] = dot(
            c_sum, w_ref[:, j * per_head + nope:(j + 1) * per_head], _NN
        ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "rank", "interpret"))
def mla_prefill_attention(q_nope: jnp.ndarray, q_rot: jnp.ndarray,
                          w_kvb: jnp.ndarray, pool: jnp.ndarray, layer,
                          first: jnp.ndarray, block_table: jnp.ndarray, *,
                          scale: float, rank: int,
                          interpret: bool = False) -> jnp.ndarray:
    """Absorbed latent attention of a chunk of T queries a row, causal by
    position, over the row's pages of ``pool``, the chunk's own rows among
    them (the caller writes them first).

    q_nope (B, T, Hq, nope), q_rot (B, T, Hq, rope): the chunk's scaled,
    rotated queries, query ``t`` of a row at position ``first + t``; w_kvb
    (rank, Hq, nope + v) ``kv_b_proj``; pool (L, N, Bs, 1, lanes) with
    ``lanes`` >= rank + rope, the padding zero; first (B,) each row's first
    position; block_table (B, max_blocks). Returns (B, T, Hq, v): per head
    ``W_UV`` applied to the softmax-weighted sum of ``c`` over positions
    ``<=`` the query's."""
    b, t, hq, nope = q_nope.shape
    _, n, bs, _, lanes = pool.shape
    mb = block_table.shape[1]
    per_head = w_kvb.shape[2]
    v = per_head - nope
    dt = q_nope.dtype
    hb = tile_heads(hq, t)
    # the rotary queries a head at a time, in the lanes behind the rank
    q_rot = jnp.pad(q_rot.transpose(0, 2, 1, 3), (
        (0, 0), (0, 0), (0, 0), (0, lanes - rank - q_rot.shape[-1])))
    pages = block_pages(bs, lanes, pool.dtype, mb)
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), first.astype(jnp.int32),
        block_table.astype(jnp.int32).reshape(-1)])
    kernel = functools.partial(_kernel, scale=scale, bs=bs, mb=mb, rank=rank,
                               nope=nope)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hq // hb),
            in_specs=[
                pl.BlockSpec((1, t, hb * nope), lambda bi, qi, sc: (bi, 0, qi)),
                pl.BlockSpec((1, hb, t, lanes - rank),
                             lambda bi, qi, sc: (bi, qi, 0, 0)),
                pl.BlockSpec((rank, hb * per_head),
                             lambda bi, qi, sc: (0, qi)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, t, hb * v),
                                   lambda bi, qi, sc: (bi, 0, qi)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hb * t, lanes), dt),
                pltpu.VMEM((hb * t, 1), jnp.float32),
                pltpu.VMEM((hb * t, 1), jnp.float32),
                pltpu.VMEM((hb * t, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, hq * v), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=MLA_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="mla_prefill_attention",
    )(scalars, q_nope.reshape(b, t, hq * nope), q_rot,
      w_kvb.reshape(rank, hq * per_head),
      pool.reshape(pool.shape[0], n, bs, lanes))
    return out.reshape(b, t, hq, v)


def chunk_attention(spec, q_nope, q_rot, w_kvb, pool, layer, positions,
                    block_table, xla_form: str):
    """The call site's whole decision (``model_base._mla_paged_block``, a
    chunk of T > 1): the kernel's result (B, T, Hq, v) and its plan in the
    engagement record, or None and ``xla_form`` + why it was declined there.
    The rows are the kernel's grid and nothing of ``heads x T`` a row leaves
    it but the result: a full-batch pack needs no row groups."""
    b, t, hq, _ = q_nope.shape
    why = ("decode_kernel=False" if spec.decode_kernel is False
           else declined(spec, pool, block_table, t))
    if why:
        kernel_mode.note("mla_prefill", "xla", f"{xla_form} ({why})")
        return None
    kernel_mode.note("mla_prefill", kernel_mode.kernel_path(),
                     plan_note(pool, hq, b, t))
    return mla_prefill_attention(
        q_nope, q_rot, w_kvb, pool, layer, positions[:, 0], block_table,
        scale=spec.scale, rank=spec.mla.kv_lora_rank,
        interpret=kernel_mode.pallas_interpret())
