"""Pallas paged decode attention over a LATENT pool (Multi-head Latent
Attention: deepseek_v2/v3, longcat_flash), in the absorbed form.

What a token leaves in the pool is one row a layer, ``[c | rot(k_rope) |
0]`` (``modules/block_kv_cache.latent_lanes`` lanes: 512 + 64 padded to 640).
Every head's K-nope and V are projections of ``c`` through ``kv_b_proj =
[W_UK | W_UV]``, so a decode step need not expand them: with ``q_lat = W_UK^T
q_nope`` (rank lanes a head) a head's score against a cached token is ``q_lat .
c + q_rot . k_rope`` - ONE dot of the head's ``[q_lat | q_rot | 0]`` row with
the token's row - and its output is ``W_UV (sum_t p_t c_t)``: the first
``rank`` lanes of the same rows double as values, and ``W_UV`` is applied
once, after the softmax, outside the kernel. A cached token costs its row's
bytes once for all heads (1,280 B against 40,960 B of expanded heads) and
2 x Hq x (lanes + rank) FLOP: at 64 heads 121 FLOP a useful byte, half the
v5e's ridge, so the kernel is bound by its bytes only while the MXU holds
half its peak - which is why it is a kernel of its own and not a flag on the
GQA one (``ops/decode_attention.py``: 2 to 8 FLOP a byte). At 128 heads
(DeepSeek-V3) it is 242 FLOP a byte, AT the ridge of 240: the two sides of
its roofline are 1.414 ns (FLOP) and 1.407 ns (bytes) a cached token a
layer (the stored row of 640 lanes: 1.563). A call with its two folds took
5.0 ns a live token at mixed row lengths and 3.8 at 8192 a row (64 heads, at
half the multiplications, 3.9 and 2.8: my chip run, PR 47).

The pattern is the GQA kernel's (PR 33) with ONE difference (PR 53): the grid
is the rows, but the rows' blocks are ONE stream of copies. For each row the
kernel walks the row's LIVE pages in compute blocks of ``pages`` pages,
copied by hand from the pool in HBM (one async copy a live page) into a ring
of :data:`MLA_SLOTS` VMEM slots; a cursor in SMEM names the next block to
copy, runs ``slots - 1`` blocks ahead of the block computed on and crosses
from a row's last block to the next LIVE row's first (a row of length 0
neither starts nor awaits a copy), so only the call's first block arrives
uncovered; ring, semaphores and cursor carry across grid steps (the grid
dimension is "arbitrary"). Blocks before a row's last are whole: their
copies and waits take no branch a page and their scores no position mask.
Layer, lengths and the whole block table ride in SMEM. The step's own token
joins in registers (its row is written to the pool by the caller; the kernel
masks ``kpos < pos``).

Which operand the MXU holds still is read from the head count
(:func:`heads_held`): at whole tiles of 128 heads the row's folded queries
are latched (5 tiles of 128 x 128) and a block's tokens stream through them,
``s_T = rows . q^T`` (tokens, heads), the running maximum and sum reduce down
the sublanes, and the accumulator is transposed, ``o_T (rank, heads) += c^T .
p_T``; at fewer heads the tokens are latched and the heads stream (the
parent's roles). Arithmetic: bf16 operands with float32 accumulation for a
bf16 pool (the probabilities cast for ``p . c``, no earlier), float32 at
HIGHEST for a float32 pool, float32 maximum / sum / accumulator, the own
token joined in float32, the sums leaving in the queries' dtype.

**What a call costs, placed (ISSUE 53; my chip runs, PR 53, one v5e; the
kernel ALONE, ``scripts/mla_decode_time.py``; pages of 32 tokens, bf16, mixed
lengths; ms a call; parent = the kernel before PR 53 with the same stops):**

====================================  ======  ======  ======  ===========
shape                                 copies  +score  whole   ns a token
====================================  ======  ======  ======  ===========
128 heads, 32 rows, 123k tok: parent  0.279   0.357   0.468   3.80
  change (heads held, blocks of 512)  0.256   0.278   0.413   3.35
64 heads, 28 of 32 rows, 104k: parent 0.245   0.270   0.318   3.06
  change (tokens held, blocks 1,024)  0.220   0.222   0.243   2.33
====================================  ======  ======  ======  ===========

* **The copies alone take 2.3 ns a token, not 1.56:** a copy of 40 KB a page
  delivers ~600 GB/s and nothing about the ring moves it (2 / 3 / 4 / 8 slots,
  blocks of 256 / 512 / 1,024 tokens: 0.260-0.270; two pages a copy: 0.266;
  HALF a page a copy: 0.186, so the bytes bind, not the descriptors). The
  next row's first block under this row's last took 0.02 off.
* **Neither dot is starved by its tile loads** (the issue's first suspect):
  with NO copies the whole body takes 0.333 ms at 128 heads (2.7 ns a token)
  and 0.223 at 64; the score dot costs 0.023-0.026 ms a K tile of 128 lanes
  (five: ~85 % of the MXU's peak), ``p . c`` 0.10 (80 %), the softmax's vector
  work 0.03, and a split of either dot into 2 or 4 row tiles changes nothing.
  Holding the heads still is worth 0.03 at 128 heads (the score stage 0.237
  -> 0.201) and COSTS 0.07 at 64, where a (tokens, 64) tile fills half of
  every vreg and half of the MXU's columns: hence the choice by shape.
* **A call is the larger of its copies and its compute plus 0.05-0.07 that
  hides under neither:** a copy's start is ~14 cycles of the scalar core
  (3,850 a call; half as many starts: -0.025), one wait for a slot's bytes
  in place of 16 gains 0.005. So 128 heads are COMPUTE-bound at 2.7 + 0.6 ns a
  token (the FLOP floor 1.41 is 80 % dots + the vector work + the starts),
  64 heads COPY-bound at 2.1 + 0.2; what moved 64 heads is the block of 1,024
  tokens (0.263 -> 0.247: the tile is then 128 heads' at 512), the third slot
  and the cross-row copy.
* **The starts do not hide under the MXU, and a row costs ~2 us beside its
  blocks:** with every block ``pages`` unconditional copies (indices clamped,
  the stream's tail drained: no branch between a block's waits and its dots)
  and the starts placed AFTER the waits, 128 heads read 0.422 (before the
  waits 0.427, this kernel in the same call 0.413-0.427) and 64 heads 0.258
  for 0.239 (whole edge blocks are 14 % more bytes there); ``1 / sum`` once
  a head in place of a division a lane: -0.003. The same tokens in 8 rows of
  ~15k take 3.03 ns a token where 32 rows take 3.47: a row's edge block is
  computed whole (half a block wasted a row) and its queries' transposition,
  own token and the accumulator's way out are ~1.5 us that no copy hides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import NEG_INF, PAGED_TABLE_SMEM_BYTES, _NN, _NT

_TN = (((0,), (0,)), ((), ()))        # (K, M) x (K, N) -> (M, N)

#: VMEM two slots of latent rows may take together (the decode kernel's ring
#: holds :data:`MLA_SLOTS`: half as much again)
MLA_KV_VMEM_BYTES = 4 * 1024 * 1024
#: slots of the ring the blocks are copied into: one computed on, the others
#: in flight (the third bought 5 % at 64 heads, where the copies bind)
MLA_SLOTS = 3
#: elements of a block's float32 score tile, (heads, tokens) or (tokens,
#: heads): the whole register file, as the GQA kernel's
MLA_SCORE_TILE_ELEMENTS = 64 * 1024
#: most tokens of one compute block, and the fewest tokens a page copy of it
#: (the copies are unrolled: 16 a block of 512 tokens, 32 a block of 1,024)
MLA_BLOCK_TOKENS = 1024
MLA_COPY_TOKENS = 32


def block_pages(bs: int, lanes: int, dtype, mb: int, heads: int = 128) -> int:
    """Pages of one compute block: the most that two slots fit
    :data:`MLA_KV_VMEM_BYTES`, the score tile
    :data:`MLA_SCORE_TILE_ELEMENTS` (at most :data:`MLA_BLOCK_TOKENS`),
    :data:`MLA_COPY_TOKENS` and the table allow. ALL heads go against a
    block in one pass (a second pass would pay the block's tiles again), so
    the heads set the block: 512 tokens at 128 heads, 1,024 at 64. On a v5e
    (32 rows, pages of 32 tokens, ms a call): at 128 heads 0.88 / 0.65 /
    0.59 in blocks of 128 / 256 / 512 tokens (117k live tokens, PR 47: what
    a block costs beside its multiplications is paid per block) and 0.415 /
    0.480 at 512 / 1,024 (123k, PR 53: past the register file the tile
    spills); at 64 heads 0.263 / 0.247 at 512 / 1,024 (104k, PR 53)."""
    page_bytes = bs * lanes * jnp.dtype(dtype).itemsize
    tokens = min(MLA_BLOCK_TOKENS, MLA_SCORE_TILE_ELEMENTS // heads)
    return max(1, min(MLA_KV_VMEM_BYTES // (2 * page_bytes), tokens // bs,
                      tokens // MLA_COPY_TOKENS, mb))


def plan_note(pool: jnp.ndarray, heads: int) -> str:
    """The engagement record's text: what a call over ``pool`` runs with."""
    _, _, bs, _, lanes = pool.shape
    pages = block_pages(bs, lanes, pool.dtype, 1 << 30, heads)
    return (f"latent lanes={lanes} heads={heads} form=absorbed pages={pages} "
            f"tiles={'heads' if heads_held(heads) else 'tokens'}-held "
            "prefetch=across-rows")


def declined(spec, pool: jnp.ndarray, block_table: jnp.ndarray) -> str:
    """Why a decode step of ``spec`` over the latent ``pool`` does not take
    the kernel ("" = it does), read from what the call shows: the pool's
    dtype and lanes, the table's size, the ambient mesh."""
    if pool.dtype not in (jnp.bfloat16, jnp.float32):
        return f"latent pool stored as {pool.dtype}"
    if spec.kv_scale not in (None, 1.0):
        return "scaled KV quantization"
    if pool.shape[4] % 128 or spec.mla.kv_lora_rank % 128:
        return "latent rows or rank not whole vregs"
    if spec.attn_soft_cap is not None or spec.attn_sink or spec.alibi \
            or spec.sliding_window:
        return "soft cap / sink / alibi / window"
    mesh = jax.sharding.get_abstract_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    if wide:
        return "mesh axes wider than one: " + ",".join(wide)
    b, mb = block_table.shape
    if 4 * (1 + b + b * mb) > PAGED_TABLE_SMEM_BYTES:
        return "block table over the SMEM a core can stage"
    return ""


def heads_held(heads: int) -> bool:
    """Which operand of the two dots the MXU holds still, read from the
    shape: a head count of whole 128-lane tiles fills the MXU's columns, so
    the row's folded queries are latched and the block's tokens stream
    (``tiles=heads-held``); fewer heads keep the tokens latched and stream
    the heads (``tiles=tokens-held``: at 64 heads the held form was the
    slower by a fifth, the table in the module docstring)."""
    return heads % 128 == 0


def _kernel(sc_ref, q_ref, new_ref, lat_hbm, o_ref, buf, sem, cur, *,
            scale: float, bs: int, mb: int, rank: int, held: bool,
            parts: str = "whole"):
    """One grid step is one ROW; the rows' blocks are ONE stream of copies.
    Scalar prefetch: [layer, len_0..len_{B-1}, table_{0,0}..,
    table_{B-1,mb-1}]. ``lat_hbm`` (L, N, bs, lanes) stays in HBM; ``buf``
    (slots, pages, bs, lanes) is a ring of slots, ``sem`` one DMA semaphore
    a slot, ``cur`` (SMEM) [row, block, count] of the next block to copy
    and the count of blocks computed: all three carry across grid steps.
    q_ref (1, Hq, lanes) = a head's ``[q_lat | q_rot | 0]``; new_ref (1, 1,
    lanes) the step's own row; o_ref (1, Hq, rank) the softmax-weighted sum
    of ``c``. Where the heads are ``held`` the row's queries are transposed
    once, (lanes, Hq), every statistic is (1, Hq) and the accumulator (rank,
    Hq), transposed back once as it leaves.
    ``parts`` stops the block short (``scripts/mla_decode_time.py``: "copies"
    walks and waits, "scores" adds the score dot and its maximum)."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = sc_ref[0]
    slots, pages, _, lanes = buf.shape
    cols = pages * bs
    hq = q_ref.shape[1]
    bf16 = buf.dtype == jnp.bfloat16

    def dot(x, w, dims):
        if bf16:
            return jax.lax.dot_general(
                x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), dims,
                preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            x.astype(jnp.float32), w.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def next_live(r):
        """The first row at or after ``r`` with a cached token (``nb``:
        none): a dead or pad row neither starts nor awaits a copy."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(r < nb, sc_ref[1 + r] == 0),
            lambda r: r + 1, r)

    def pages_of(i, last_live, do, whole):
        """``do(p)`` for the pages of block ``i`` of a row whose last live
        page is ``last_live``: all of them where the block is ``whole`` (no
        branch a page), else the live ones."""
        for p in range(pages):
            if whole:
                do(p)
            else:
                pl.when(i * pages + p <= last_live)(functools.partial(do, p))

    def issue():
        """Start the copies of the stream's next block, if one is left,
        into the slot its count names, and move ``cur`` on."""
        r, i, count = cur[0], cur[1], cur[2]

        @pl.when(r < nb)
        def _():
            length = sc_ref[1 + r]
            last_live = jax.lax.div(length - 1, bs)
            table0 = 1 + nb + r * mb + i * pages
            slot = jax.lax.rem(count, slots)

            def start(p):
                pltpu.make_async_copy(
                    lat_hbm.at[layer, sc_ref[table0 + p]], buf.at[slot, p],
                    sem.at[slot]).start()
            done = (i + 1) * cols >= length
            pl.when(done)(lambda: pages_of(i, last_live, start, False))
            pl.when(jnp.logical_not(done))(
                lambda: pages_of(i, last_live, start, True))
            cur[0] = next_live(r + done.astype(jnp.int32))   # r itself is live
            cur[1] = jnp.where(done, 0, i + 1)
            cur[2] = count + 1

    @pl.when(b == 0)
    def _open():
        # a page no copy fills is multiplied by a probability of 0: finite
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        cur[0] = next_live(0)
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0
        for _ in range(slots - 1):
            issue()

    pos = sc_ref[1 + b]
    n_blocks = jax.lax.div(pos + cols - 1, cols)
    q = q_ref[0].T if held else q_ref[0]
    # the heads held: reductions run down the sublanes, not across lanes
    ax = 0 if held else 1
    tok = jax.lax.broadcasted_iota(
        jnp.int32, (cols, hq) if held else (hq, cols), ax)

    def block(i, carry, edge):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(cur[3], slots)
        cur[3] = cur[3] + 1
        issue()     # slots - 1 blocks ahead, the next rows' among them
        pages_of(i, jax.lax.div(pos - 1, bs), lambda p: pltpu.make_async_copy(
            lat_hbm.at[layer, 0], buf.at[slot, p], sem.at[slot]).wait(),
            not edge)
        rows = buf[slot].reshape(cols, lanes)
        if parts == "copies":
            return m_prev, l_prev + rows[:1, :1].astype(jnp.float32), acc
        s = (dot(rows, q, _NN) if held else dot(q, rows, _NT)) * scale
        if edge:    # only a row's last block holds a token past ``pos``
            s = jnp.where(tok < pos - i * cols, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=ax, keepdims=True))
        if parts == "scores":
            return m_cur, l_prev, acc
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=ax, keepdims=True)
        c = rows[:, :rank]
        return m_cur, l_cur, acc * alpha + (
            dot(c, p, _TN) if held else dot(p, c, _NN))

    stat = (1, hq) if held else (hq, 1)
    carry = jax.lax.fori_loop(
        0, n_blocks - 1, functools.partial(block, edge=False),
        (jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
         jnp.zeros((rank, hq) if held else (hq, rank), jnp.float32)))
    m_prev, l_prev, acc = jax.lax.cond(
        n_blocks > 0, lambda c: block(n_blocks - 1, c, True), lambda c: c,
        carry)

    # the step's own token joins in registers
    new = new_ref[0].astype(jnp.float32)                        # (1, lanes)
    if held:
        new = jnp.broadcast_to(new, (hq, lanes)).T              # (lanes, Hq)
    s = jnp.sum(q.astype(jnp.float32) * new, axis=ax, keepdims=True) * scale
    m_cur = jnp.maximum(m_prev, s)
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    out = ((acc * alpha + p * (new[:rank] if held else new[:, :rank]))
           / (l_prev * alpha + p))
    o_ref[0] = (out.T if held else out).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def latent_rows_attention(q_row: jnp.ndarray, new_row: jnp.ndarray,
                          pool: jnp.ndarray, layer, lens: jnp.ndarray,
                          block_table: jnp.ndarray, *, scale: float,
                          rank: int, interpret: bool = False) -> jnp.ndarray:
    """The Pallas call alone (``scripts/mla_decode_time.py`` times it):
    q_row (B, Hq, lanes) the folded queries ``[q_lat | q_rot | 0]``, new_row
    (B, 1, lanes). Returns (B, Hq, rank), the softmax-weighted sums of ``c``
    in the queries' dtype."""
    b, hq, lanes = q_row.shape
    _, n, bs, _, _ = pool.shape
    mb = block_table.shape[1]
    pages = block_pages(bs, lanes, pool.dtype, mb, hq)
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), lens.astype(jnp.int32),
        block_table.astype(jnp.int32).reshape(-1)])
    kernel = functools.partial(_kernel, scale=scale, bs=bs, mb=mb, rank=rank,
                               held=heads_held(hq))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hq, lanes), lambda bi, sc: (bi, 0, 0)),
                pl.BlockSpec((1, 1, lanes), lambda bi, sc: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hq, rank), lambda bi, sc: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((MLA_SLOTS, pages, bs, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((MLA_SLOTS,)),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, rank), q_row.dtype),
        # the ring and its cursor carry from a row to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode_attention",
    )(scalars, q_row, new_row, pool.reshape(pool.shape[0], n, bs, lanes))


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def mla_decode_attention(q_nope: jnp.ndarray, q_rot: jnp.ndarray,
                         new_lat: jnp.ndarray, w_kvb: jnp.ndarray,
                         pool: jnp.ndarray, layer, lens: jnp.ndarray,
                         block_table: jnp.ndarray, *, scale: float,
                         rank: int, interpret: bool = False) -> jnp.ndarray:
    """Absorbed latent decode attention of one token a row.

    q_nope (B, Hq, nope), q_rot (B, Hq, rope): the step's rotated queries;
    new_lat (B, rank + rope) its own latent rows as stored; w_kvb
    (rank, Hq, nope + v) ``kv_b_proj``; pool (L, N, Bs, 1, lanes) with
    ``lanes`` >= rank + rope, the padding zero; lens (B,) prior lengths;
    block_table (B, max_blocks). Returns (B, Hq, v): per head ``W_UV``
    applied to the softmax-weighted sum of the live rows' ``c`` and the
    step's own."""
    nope = q_nope.shape[2]
    lanes = pool.shape[4]
    dt = q_nope.dtype
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_kvb[..., :nope],
                       preferred_element_type=jnp.float32).astype(dt)
    pad = lanes - rank - q_rot.shape[-1]
    q_row = jnp.pad(jnp.concatenate([q_lat, q_rot], axis=-1),
                    ((0, 0), (0, 0), (0, pad)))
    new_row = jnp.pad(new_lat, ((0, 0), (0, lanes - new_lat.shape[-1]))
                      )[:, None, :]
    acc = latent_rows_attention(q_row, new_row, pool, layer, lens,
                                block_table, scale=scale, rank=rank,
                                interpret=interpret)
    return jnp.einsum("bhr,rhd->bhd", acc, w_kvb[..., nope:],
                      preferred_element_type=jnp.float32).astype(dt)
