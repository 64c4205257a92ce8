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
layer, and the MXU's share of its peak sets the time: a call with its two
folds took 5.0 ns a live token at mixed row lengths and 3.8 at 8192 a row
(28 and 37 % of that roofline; 64 heads, at half the multiplications, 3.9
and 2.8: my chip run, PR 47).

The pattern is that kernel's (PR 33): the grid is the rows; for each the
kernel walks the row's LIVE pages in compute blocks of ``pages`` pages,
copied by hand from the pool in HBM (one async copy a page) into one of two
VMEM slots while the other is computed on; layer, lengths and the whole block
table ride in SMEM. The step's own token joins in registers (its row is
written to the pool by the caller; the kernel masks ``kpos < pos``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (NEG_INF, PAGED_BLOCK_PAGES,
                               PAGED_TABLE_SMEM_BYTES, _NN, _NT)

#: VMEM the kernel spends on its two slots of latent rows together
MLA_KV_VMEM_BYTES = 4 * 1024 * 1024
#: most tokens of one compute block: the (heads, tokens) float32 score tile
#: of 64 heads is then 32 vregs, as the GQA kernel's
MLA_BLOCK_TOKENS = 512


def block_pages(bs: int, lanes: int, dtype, mb: int) -> int:
    """Pages of one compute block: the most that two slots fit
    :data:`MLA_KV_VMEM_BYTES`, :data:`MLA_BLOCK_TOKENS` and
    ``PAGED_BLOCK_PAGES`` (the copies are unrolled) and the table allow.
    The heads do not enter: ALL of them go against a block in one pass (a
    head is a row of the MXU's moving operand; a second pass would pay the
    block's tiles again), and at 128 heads (DeepSeek-V3), where the score
    tile of 512 tokens is the whole register file, a shorter block is
    SLOWER: 32 rows over 117k live tokens took 0.88 / 0.65 / 0.59 ms a call
    in blocks of 128 / 256 / 512 tokens on a v5e (PERF.md section 6, PR 47:
    what a block costs beside its multiplications is paid per block)."""
    page_bytes = bs * lanes * jnp.dtype(dtype).itemsize
    return max(1, min(MLA_KV_VMEM_BYTES // (2 * page_bytes),
                      MLA_BLOCK_TOKENS // bs, PAGED_BLOCK_PAGES, mb))


def plan_note(pool: jnp.ndarray, heads: int) -> str:
    """The engagement record's text: what a call over ``pool`` runs with."""
    _, _, bs, _, lanes = pool.shape
    return (f"latent lanes={lanes} heads={heads} form=absorbed "
            f"pages={block_pages(bs, lanes, pool.dtype, 1 << 30)}")


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


def _kernel(sc_ref, q_ref, new_ref, lat_hbm, o_ref, buf, sem, *,
            scale: float, bs: int, mb: int, rank: int):
    """One grid step is one ROW. Scalar prefetch: [layer, len_0..len_{B-1},
    table_{0,0}.., table_{B-1,mb-1}]. ``lat_hbm`` (L, N, bs, lanes) stays in
    HBM; ``buf`` (2, pages, bs, lanes) are the two slots. q_ref (1, Hq,
    lanes) = a head's ``[q_lat | q_rot | 0]``; new_ref (1, 1, lanes) the
    step's own row; o_ref (1, Hq, rank) the softmax-weighted sum of ``c``."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = sc_ref[0]
    pos = sc_ref[1 + b]
    last_live = jax.lax.div(jnp.maximum(pos - 1, 0), bs)
    n_pages = jnp.where(pos > 0, last_live + 1, 0)
    _, pages, _, lanes = buf.shape
    n_blocks = jax.lax.div(n_pages + pages - 1, pages)
    table0 = 1 + nb + b * mb
    hq = q_ref.shape[1]
    cols = pages * bs
    bf16 = buf.dtype == jnp.bfloat16

    def dot(x, w, dims):
        if bf16:
            return jax.lax.dot_general(x.astype(jnp.bfloat16), w, dims,
                                       preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            x.astype(jnp.float32), w.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def page_copies(i, slot):
        for p in range(pages):
            j = i * pages + p
            page = sc_ref[table0 + jnp.minimum(j, last_live)]
            yield p, j <= last_live, pltpu.make_async_copy(
                lat_hbm.at[layer, page], buf.at[slot, p], sem.at[slot])

    def start(i, slot):
        for p, live, copy in page_copies(i, slot):
            @pl.when(live)
            def _fetch():
                copy.start()

            @pl.when(jnp.logical_not(live))
            def _blank():
                # a page past the row's end is computed on (masked): its
                # lanes double as values and must be finite
                buf[slot, p] = jnp.zeros((bs, lanes), buf.dtype)

    def wait(i, slot):
        for p, live, copy in page_copies(i, slot):
            @pl.when(live)
            def _landed():
                copy.wait()

    tok = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
    q = q_ref[0]

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _next():
            start(i + 1, 1 - slot)

        wait(i, slot)
        rows = buf[slot].reshape(cols, lanes)
        s = dot(q, rows, _NT) * scale
        s = jnp.where(i * cols + tok < pos, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        return (m_cur, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + dot(p, rows[:, :rank], _NN))

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    m_prev, l_prev, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((hq, 1), NEG_INF, jnp.float32),
        jnp.zeros((hq, 1), jnp.float32), jnp.zeros((hq, rank), jnp.float32)))

    # the step's own token joins in registers
    new = new_ref[0].astype(jnp.float32)                        # (1, lanes)
    s = jnp.sum(q.astype(jnp.float32) * new, axis=-1, keepdims=True) * scale
    m_cur = jnp.maximum(m_prev, s)
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    o_ref[0] = ((acc * alpha + p * new[:, :rank])
                / (l_prev * alpha + p)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "rank", "interpret"))
def mla_decode_attention(q_nope: jnp.ndarray, q_rot: jnp.ndarray,
                         new_lat: jnp.ndarray, w_kvb: jnp.ndarray,
                         pool: jnp.ndarray, layer, lens: jnp.ndarray,
                         block_table: jnp.ndarray, *, scale: float,
                         rank: int, interpret: bool = False) -> jnp.ndarray:
    """Absorbed latent decode attention of one token a row.

    q_nope (B, Hq, nope), q_rot (B, Hq, rope): the step's scaled, rotated
    queries; new_lat (B, rank + rope) its own latent rows as stored; w_kvb
    (rank, Hq, nope + v) ``kv_b_proj``; pool (L, N, Bs, 1, lanes) with
    ``lanes`` >= rank + rope, the padding zero; lens (B,) prior lengths;
    block_table (B, max_blocks). Returns (B, Hq, v): per head ``W_UV``
    applied to the softmax-weighted sum of the live rows' ``c`` and the
    step's own."""
    b, hq, nope = q_nope.shape
    _, n, bs, _, lanes = pool.shape
    mb = block_table.shape[1]
    dt = q_nope.dtype
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_kvb[..., :nope],
                       preferred_element_type=jnp.float32).astype(dt)
    pad = lanes - rank - q_rot.shape[-1]
    q_row = jnp.pad(jnp.concatenate([q_lat, q_rot], axis=-1),
                    ((0, 0), (0, 0), (0, pad)))
    new_row = jnp.pad(new_lat, ((0, 0), (0, lanes - new_lat.shape[-1]))
                      )[:, None, :]
    pages = block_pages(bs, lanes, pool.dtype, mb)
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), lens.astype(jnp.int32),
        block_table.astype(jnp.int32).reshape(-1)])
    kernel = functools.partial(_kernel, scale=scale, bs=bs, mb=mb, rank=rank)
    acc = pl.pallas_call(
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
                pltpu.VMEM((2, pages, bs, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, rank), jnp.float32),
        interpret=interpret,
        name="mla_decode_attention",
    )(scalars, q_row, new_row, pool.reshape(pool.shape[0], n, bs, lanes))
    return jnp.einsum("bhr,rhd->bhd", acc.astype(dt), w_kvb[..., nope:],
                      preferred_element_type=jnp.float32).astype(dt)
