"""Pallas few-token expert kernel: the experts of a step of at most
``dense_max_tokens`` tokens (a decode step, a one-row 64-token chunk), read
where they lie in their stack, and only those the routing touched
(reference: the all-experts decode kernel ``moe_token_gen``, SURVEY §2.10,
whose dense form ``modules/moe.py`` ``experts_dense`` keeps for what this
kernel declines).

The dense path streams EVERY held expert of a layer whatever the routing
chose; it does so near the chip's bandwidth, so the only gain left is to
read less. An untouched expert's term of the combine-weighted sum is exactly
zero there, so walking the touched experts alone is the same sum in another
order: no precision, no expert and no row is dropped. What lies where:

* SMEM (scalar prefetch): the layer, the touched experts' count and their
  ids, compacted to the front (:func:`touched_experts`, from the combine
  matrix of ALL rows of the step).
* HBM: the three stacked expert leaves (L, E, H, I) / (L, E, I, H),
  untouched (``memory_space=pl.ANY``): the layer and the expert are indexed
  by hand, so no slice is cut in front of the custom call (a slice there is
  a copy of a layer's experts on every call, PERF.md §6, PR 31).
* VMEM: two slots of one unit's three matrices. A unit is one expert, or
  one of ``pieces`` column pieces of its intermediate dimension where a
  whole expert does not fit :data:`MOE_WEIGHT_VMEM_BYTES`
  (:func:`moe_decode_plan`): gate and up lose columns (runs of ``ip``
  elements in HBM), down the same rows (contiguous), and a piece's
  ``glu(x Wg, x Wu) Wd`` is a term of the expert's. One slot is copied into
  while the other is computed on; the loop is as long as the touched list,
  not the expert axis (a grid of E steps pays its steps whether or not they
  do anything, PERF.md §6, PR 33).
* MXU: all N rows against a unit in the operands' dtype with float32
  accumulation; times the expert's combine column in float32, summed into
  the float32 (N, H) result.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the kernel spends on its two slots of three matrices together: one
#: OLMoE expert (3 x 4 MiB in bf16) a slot. A v5e core has 128 MiB; the
#: scoped default of 16 is raised to this plus :data:`MOE_VMEM_HEADROOM_BYTES`.
MOE_WEIGHT_VMEM_BYTES = 24 * 1024 * 1024
#: beside the slots: the rows, the combine matrix, the float32 result and a
#: unit's float32 intermediates, double-buffered where the pipeline holds
#: them (64 rows x 2048: under 2 MiB), and Mosaic's own scratch.
MOE_VMEM_HEADROOM_BYTES = 8 * 1024 * 1024
#: lanes of a vreg: a piece's width and both matrix dimensions are whole
#: multiples of it
LANES = 128


class MoEDecodePlan(NamedTuple):
    """What one call of the kernel runs with (:func:`moe_decode_plan`)."""
    pieces: int         # units an expert is walked in
    ip: int             # columns of the intermediate dimension a unit

    def note(self) -> str:
        """The engagement record's text (``kernel_mode.note``)."""
        return f"pieces={self.pieces} of {self.ip}"


def moe_decode_plan(h: int, i: int, dtype) -> Optional[MoEDecodePlan]:
    """How the kernel walks experts of ``h`` x ``i``: the fewest column
    pieces of whole vregs such that two slots of a piece's three matrices
    fit :data:`MOE_WEIGHT_VMEM_BYTES`; chosen from the leaves' shape and
    item size and from nothing else. None: no such piece."""
    if h % LANES or i % LANES:
        return None
    for pieces in range(1, i // LANES + 1):
        ip, rest = divmod(i, pieces)
        if rest or ip % LANES:
            continue
        if 2 * 3 * h * ip * jnp.dtype(dtype).itemsize <= MOE_WEIGHT_VMEM_BYTES:
            return MoEDecodePlan(pieces, ip)
    return None


def declined(moe, wg: Any) -> str:
    """Why the kernel does not take a few-token step over the expert leaf
    ``wg`` (one layer's, or the stack) of ``moe`` ("" = it does). Read from
    what the code can see - the spec, the leaf, the ambient mesh - and from
    nothing else: whatever is named here keeps ``experts_dense``."""
    if isinstance(wg, dict):           # a quantized leaf: qweight + scales
        return "quantized experts"
    if wg.dtype not in (jnp.bfloat16, jnp.float32):
        return f"experts stored as {wg.dtype}"
    mesh = jax.sharding.get_abstract_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    if wide:
        # ep / tp shard the expert or the intermediate axis of the leaves;
        # under dp the bare call would run replicated on every device
        return "mesh axes wider than one: " + ",".join(wide)
    if moe.tkg_experts_local:
        return "tkg_experts_local re-lays the experts for decode"
    if moe.input_scaled:
        return "input_scaled routing scales the expert input"
    if moe.expert_bias:
        return "per-expert biases"
    if moe.glu_style != "gated" or moe.act != "silu":
        return f"glu {moe.glu_style}/{moe.act}"
    if moe_decode_plan(wg.shape[-2], wg.shape[-1], wg.dtype) is None:
        return (f"experts of {wg.shape[-2]} x {wg.shape[-1]} are not whole "
                f"{LANES}-lane tiles")
    return ""


def touched_experts(combine: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The experts with a non-zero column of ``combine`` (N, E), ascending
    and compacted to the front of an (E,) int32 vector, and their count.
    ALL rows count - live, pad and lookahead alike - so a clone of a row is
    computed as the row is. Entries past the count are never read."""
    e = combine.shape[1]
    seen = jnp.cumsum(jnp.any(combine != 0, axis=0).astype(jnp.int32))
    # the j-th touched expert is the first whose running count passes j
    ids = jnp.sum(seen[None, :] <= jnp.arange(e, dtype=jnp.int32)[:, None],
                  axis=1, dtype=jnp.int32)
    return jnp.minimum(ids, e - 1), seen[-1]


def _kernel(sc_ref, x_ref, comb_ref, wg_hbm, wu_hbm, wd_hbm, o_ref,
            gbuf, ubuf, dbuf, sem, *, pieces: int, glu: Callable):
    """Scalar prefetch (SMEM): [layer, count, id_0 .. id_{E-1}]. ``w*_hbm``
    are the whole stacks, left in HBM; unit ``u`` is piece ``u % pieces`` of
    touched expert ``u // pieces``, copied by hand (three async copies)
    into slot ``u % 2`` while the other slot is computed on."""
    layer = sc_ref[0]
    n_units = sc_ref[1] * pieces
    ip = gbuf.shape[2]

    def copies(u, slot):
        e = sc_ref[2 + jax.lax.div(u, pieces)]
        if pieces == 1:
            g_src, u_src, d_src = (w.at[layer, e]
                                   for w in (wg_hbm, wu_hbm, wd_hbm))
        else:
            cols = pl.ds(pl.multiple_of(jax.lax.rem(u, pieces) * ip, LANES),
                         ip)
            g_src = wg_hbm.at[layer, e, :, cols]
            u_src = wu_hbm.at[layer, e, :, cols]
            d_src = wd_hbm.at[layer, e, cols, :]
        return (pltpu.make_async_copy(g_src, gbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(u_src, ubuf.at[slot], sem.at[1, slot]),
                pltpu.make_async_copy(d_src, dbuf.at[slot], sem.at[2, slot]))

    def start(u, slot):
        for c in copies(u, slot):
            c.start()

    o_ref[...] = jnp.zeros_like(o_ref)
    x = x_ref[...]
    lane_expert = jax.lax.broadcasted_iota(jnp.int32, comb_ref.shape, 1)

    @pl.when(n_units > 0)
    def _first():
        start(0, 0)

    def unit(u, carry):
        slot = jax.lax.rem(u, 2)

        @pl.when(u + 1 < n_units)
        def _next():
            start(u + 1, 1 - slot)

        g_copy, u_copy, d_copy = copies(u, slot)
        g_copy.wait()
        gate = jnp.dot(x, gbuf[slot], preferred_element_type=jnp.float32)
        u_copy.wait()
        up = jnp.dot(x, ubuf[slot], preferred_element_type=jnp.float32)
        inter = glu(gate, up).astype(x.dtype)
        d_copy.wait()
        out = jnp.dot(inter, dbuf[slot], preferred_element_type=jnp.float32)
        # the expert's combine column, (N, 1): one lane of each row is live
        e = sc_ref[2 + jax.lax.div(u, pieces)]
        w = jnp.sum(jnp.where(lane_expert == e, comb_ref[...], 0.0),
                    axis=1, keepdims=True)
        o_ref[...] += out * w
        return carry

    jax.lax.fori_loop(0, n_units, unit, 0)


def moe_decode_experts(x: jnp.ndarray, combine: jnp.ndarray,
                       wg: jnp.ndarray, wu: jnp.ndarray, wd: jnp.ndarray,
                       layer, *, glu: Callable, interpret: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The combine-weighted expert sum of a few tokens over the touched
    experts of one layer of a stack.

    x (N, H) the step's tokens; combine (N, E) float32 over the experts the
    leaves hold (``moe.held_combine``); wg / wu (L, E, H, I), wd
    (L, E, I, H) the STACKED leaves and ``layer`` the layer's index (a
    Python int or a traced scalar); ``glu(gate, up)`` the gated
    nonlinearity on float32. Returns the float32 (N, H) sum and the number
    of experts read (the touched list's length)."""
    n, h = x.shape
    e, i = wg.shape[1], wg.shape[3]
    plan = moe_decode_plan(h, i, wg.dtype)
    if plan is None:
        raise ValueError(f"moe decode kernel: experts of {h} x {i} "
                         f"{wg.dtype} are not whole {LANES}-lane tiles "
                         "(moe_decode.declined says what the kernel takes)")
    ids, count = touched_experts(combine)
    # rows in whole sublane tiles of the operands' dtype; a pad row is zero
    # and weighs nothing
    rows = -n % (32 // jnp.dtype(wg.dtype).itemsize)
    x, combine = (jnp.pad(a, ((0, rows), (0, 0)))
                  for a in (x.astype(wg.dtype), combine))
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), count.reshape(1), ids])
    whole = lambda g, sc: (0, 0)                               # noqa: E731
    slot = (2, h, plan.ip)
    out = pl.pallas_call(
        functools.partial(_kernel, pieces=plan.pieces, glu=glu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((n + rows, h), whole),
                pl.BlockSpec((n + rows, e), whole),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((n + rows, h), whole),
            scratch_shapes=[
                pltpu.VMEM(slot, wg.dtype),
                pltpu.VMEM(slot, wu.dtype),
                pltpu.VMEM((2, plan.ip, h), wd.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n + rows, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=MOE_WEIGHT_VMEM_BYTES + MOE_VMEM_HEADROOM_BYTES),
        name="moe_decode_experts",
        interpret=interpret,
    )(scalars, x, combine, wg, wu, wd)
    return out[:n], count
