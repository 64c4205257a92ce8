"""Pallas expert kernels: the experts of a step whose held experts each get
FEW rows (a decode step, a one-row prefill chunk), read where they lie in
their stack, and only those the routing touched (reference: the all-experts
decode kernel ``moe_token_gen``, SURVEY §2.10, whose dense form
``modules/moe.py`` ``experts_dense`` keeps for the few tokens this kernel
declines; a chunk it declines keeps ``experts_ragged``).

The dense path streams EVERY held expert of a layer whatever the routing
chose, and the grouped matmuls of a chunk spend a whole tile of the MXU on a
group of five rows; such a step is bound by the bytes of the experts it
touches, so the gain is to read each of them once, at the chip's bandwidth,
and nothing else. An untouched expert's term of the combine-weighted sum is
exactly zero, so walking the touched experts alone is the same sum in
another order: no precision, no expert and no row is dropped. What lies
where:

* SMEM (scalar prefetch): the layer, the touched experts' count and their
  ids, compacted to the front (:func:`touched_experts`, from ALL rows of the
  step); for a chunk also the bounds of each expert's group in the step's
  assignments sorted by expert, and that list (token, combine weight).
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
  do anything, PERF.md §6, PR 33). Beside the slots, the step's rows and
  float32 result, whole: what they need is computed from the rows the call
  carries (:func:`rows_vmem_bytes`), and a step whose rows would need more
  than :data:`MOE_ROWS_VMEM_BYTES` is declined. A step of one tile rides the
  call's pipeline, which keeps two buffers of every block it is given; a
  longer one's float32 rows and result are the bulk of what it holds, so
  they stay in HBM as the call sees them and the kernel keeps ONE copy of
  each, brought in and written back by hand around the walk (256 rows of
  7168 are 14.7 MB so held, 29.4 MB in a pipeline's pairs: DeepSeek-V3,
  PR 47).
* MXU: rows against a unit in the operands' dtype with float32
  accumulation, combined in float32. A step of at most :data:`ROW_TILE`
  rows (:func:`moe_decode_experts`): ALL rows against every unit, times the
  expert's combine column. A longer one (:func:`moe_chunk_experts`, a
  one-row chunk): the unit against ITS rows only, gathered a tile at a time
  from the float32 rows, each row of the product added, times its weight,
  to its token's row of the (N, H) result.
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
#: scoped default of 16 is raised to the slots plus what the step's rows
#: need beside them (:func:`rows_vmem_bytes`), which may be as much again.
MOE_WEIGHT_VMEM_BYTES = 24 * 1024 * 1024
#: VMEM a step's rows may take beside the slots (:func:`rows_vmem_bytes`):
#: set by a one-row chunk of 256 tokens at a hidden size of 6144 when the
#: pipeline held its rows and result in pairs (39.6 MiB: LongCat-Flash,
#: PR 40); held once, that chunk needs 27.6 MiB and one of 7168 wide 32.1
#: (DeepSeek-V3, PR 47). Slots and rows together stay at half of a core's
#: 128 MiB
MOE_ROWS_VMEM_BYTES = 40 * 1024 * 1024
#: most rows of a step on the walk: timed at 256 and 512 (PERF.md section 6,
#: PR 39). A full-batch pack of 1024 rows at a hidden size of 2048 would fit
#: the bytes above too and has met no clock: it keeps the grouped matmuls
MOE_WALK_MAX_ROWS = 512

#: gate activations of a gated expert the walk computes (``moe._glu`` is
#: applied to the float32 products inside the kernel): SiLU, and ReLU
#: (SmallThinker's ReLU-gated experts). The zeros a ReLU leaves are NOT
#: exploited: every column of a touched expert is read and multiplied.
WALK_ACTS = ("silu", "relu")
#: lanes of a vreg: a piece's width and both matrix dimensions are whole
#: multiples of it
LANES = 128
#: rows of one product with a unit: the MXU holds a 128 x 128 tile of the
#: matrix while rows pass, so up to this many rows cost one pass over a
#: unit's weights, which is less than its copy. A step of at most a tile's
#: rows multiplies ALL of them by every touched expert
#: (:func:`moe_decode_experts`); a step of more multiplies an expert by ITS
#: rows, a tile at a time (:func:`moe_chunk_experts`).
ROW_TILE = 128


class MoEDecodePlan(NamedTuple):
    """What one call of the kernel runs with (:func:`moe_decode_plan`)."""
    pieces: int         # units an expert is walked in
    ip: int             # columns of the intermediate dimension a unit

    def note(self, rows: int = 1) -> str:
        """The engagement record's text (``kernel_mode.note``) for a step
        of ``rows`` rows."""
        by_expert = (f" rows={rows} by expert in tiles of {ROW_TILE}"
                     if rows > ROW_TILE else "")
        return f"pieces={self.pieces} of {self.ip}{by_expert}"


def moe_decode_plan(h: int, i: int, dtype,
                    matrices: int = 3) -> Optional[MoEDecodePlan]:
    """The fewest column pieces of whole vregs such that two slots of a
    piece's ``matrices`` fit :data:`MOE_WEIGHT_VMEM_BYTES`. None: no piece."""
    if h % LANES or i % LANES:
        return None
    per_matrix = MOE_WEIGHT_VMEM_BYTES // (2 * matrices)
    for pieces in range(1, i // LANES + 1):
        ip, rest = divmod(i, pieces)
        if rest or ip % LANES:
            continue
        if h * ip * jnp.dtype(dtype).itemsize <= per_matrix:
            return MoEDecodePlan(pieces, ip)
    return None


def rows_vmem_bytes(n: int, h: int, e: int, plan: MoEDecodePlan,
                    dtype) -> int:
    """VMEM a step of ``n`` rows of ``h`` needs beside the slots, from the
    shapes the call carries: what a step of one tile leaves to the
    pipeline, which holds it twice (the rows, the (n, e) float32 combine
    matrix and the float32 result); the float32 rows and result of a longer
    one, held once by the kernel, and its two tiles; and a product's
    float32 intermediates, counted twice for what Mosaic keeps of them."""
    item = jnp.dtype(dtype).itemsize
    tile = min(n, ROW_TILE)
    if n <= ROW_TILE:
        held = 2 * n * (h * item + e * 4 + h * 4)
    else:
        held = 2 * n * h * 4 + 2 * tile * h * 4
    product = tile * (h * item + 2 * plan.ip * 4 + plan.ip * item + h * 4)
    return held + 2 * product


def declined(moe, wg: Any, tokens: int = 1) -> str:
    """Why the kernel does not take a step of ``tokens`` tokens over the
    expert leaf ``wg`` (one layer's, or the stack) of ``moe`` ("" = it
    does). Read from what the code can see - the spec, the leaf, the
    ambient mesh - and from nothing else: whatever is named here keeps
    ``experts_dense`` for few tokens and ``experts_ragged`` for a chunk."""
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
    if not walks_glu(moe):
        return f"glu {moe.glu_style}/{moe.act}"
    h, i = wg.shape[-2:]
    plan = moe_decode_plan(h, i, wg.dtype, matrices_of(moe))
    if plan is None:
        return f"experts of {h} x {i} are not whole {LANES}-lane tiles"
    if tokens > MOE_WALK_MAX_ROWS or rows_vmem_bytes(
            tokens, h, wg.shape[-3], plan, wg.dtype) > MOE_ROWS_VMEM_BYTES:
        return f"{tokens} rows of {h} do not fit VMEM beside the slots"
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


def _walk(sc_ref, stacks, slots, sem, pieces: int, compute: Callable):
    """The walk both kernels share. ``sc_ref`` (SMEM) starts [layer, count,
    id_0 .. id_{E-1}]; ``stacks`` are the three whole leaves, left in HBM.
    Unit ``u`` is piece ``u % pieces`` of touched expert ``u // pieces``,
    copied by hand (three async copies) into slot ``u % 2`` of ``slots``
    while the other slot is computed on. ``compute(expert, slot, copies)``
    waits for each of the unit's three copies, once, where it first needs
    its matrix."""
    layer = sc_ref[0]
    n_units = sc_ref[1] * pieces
    ip = slots[0].shape[2]

    def copies(u, slot):
        e = sc_ref[2 + jax.lax.div(u, pieces)]
        if pieces == 1:
            srcs = tuple(w.at[layer, e] for w in stacks)
        else:
            wg_hbm, wu_hbm, wd_hbm = stacks
            cols = pl.ds(pl.multiple_of(jax.lax.rem(u, pieces) * ip, LANES),
                         ip)
            srcs = (wg_hbm.at[layer, e, :, cols],
                    wu_hbm.at[layer, e, :, cols],
                    wd_hbm.at[layer, e, cols, :])
        return tuple(
            pltpu.make_async_copy(src, buf.at[slot], sem.at[k, slot])
            for k, (src, buf) in enumerate(zip(srcs, slots)))

    def start(u, slot):
        for c in copies(u, slot):
            c.start()

    @pl.when(n_units > 0)
    def _first():
        start(0, 0)

    def unit(u, carry):
        slot = jax.lax.rem(u, 2)

        @pl.when(u + 1 < n_units)
        def _next():
            start(u + 1, 1 - slot)

        compute(sc_ref[2 + jax.lax.div(u, pieces)], slot, copies(u, slot))
        return carry

    jax.lax.fori_loop(0, n_units, unit, 0)


def _expert(x, slot, slots, glu: Callable, copies=()):
    """``glu(x Wg, x Wu) Wd`` of the rows ``x`` on the unit in ``slot``, in
    float32; a copy still in flight (``copies``) is waited for where its
    matrix is first read."""
    gbuf, ubuf, dbuf = slots

    def arrived(k):
        if copies:
            copies[k].wait()

    arrived(0)
    gate = jnp.dot(x, gbuf[slot], preferred_element_type=jnp.float32)
    arrived(1)
    up = jnp.dot(x, ubuf[slot], preferred_element_type=jnp.float32)
    inter = glu(gate, up).astype(x.dtype)
    arrived(2)
    return jnp.dot(inter, dbuf[slot], preferred_element_type=jnp.float32)


def _kernel(sc_ref, x_ref, comb_ref, wg_hbm, wu_hbm, wd_hbm, o_ref,
            gbuf, ubuf, dbuf, sem, *, pieces: int, glu: Callable):
    """A step whose rows are one tile: ALL of them against every unit."""
    o_ref[...] = jnp.zeros_like(o_ref)
    x = x_ref[...]
    lane_expert = jax.lax.broadcasted_iota(jnp.int32, comb_ref.shape, 1)
    slots = (gbuf, ubuf, dbuf)

    def compute(e, slot, copies):
        out = _expert(x, slot, slots, glu, copies)
        # the expert's combine column, (N, 1): one lane of each row is live
        w = jnp.sum(jnp.where(lane_expert == e, comb_ref[...], 0.0),
                    axis=1, keepdims=True)
        o_ref[...] += out * w

    _walk(sc_ref, (wg_hbm, wu_hbm, wd_hbm), slots, sem, pieces, compute)


def _rows_kernel(sc_ref, tok_ref, wt_ref, x_hbm, wg_hbm, wu_hbm, wd_hbm,
                 o_hbm, gbuf, ubuf, dbuf, xt, yt, x_ref, o_ref, io_sem, sem,
                 *, pieces: int, glu: Callable, experts: int):
    """A step of more rows than a tile: each unit against ITS rows. Behind
    the touched list ``sc_ref`` holds the E + 1 bounds of the experts'
    groups in the assignment list; ``tok_ref`` / ``wt_ref`` (SMEM) are that
    list, sorted by expert: a row of ``x_ref`` (float32, so that a row is
    one sublane) and its combine weight. A group is gathered a tile of
    rows at a time into ``xt`` and multiplied, and each row of the product
    (``yt``) is added, times its weight, to its token's row of the result.
    Rows of a tile past the group's end are an earlier tile's: computed,
    never added. The rows ``x_hbm`` and the result ``o_hbm`` are the call's,
    in HBM; ``x_ref`` / ``o_ref`` are the kernel's one copy of each, filled
    before the walk and written back after it."""
    rows_in = pltpu.make_async_copy(x_hbm, x_ref, io_sem.at[0])
    rows_in.start()
    o_ref[...] = jnp.zeros_like(o_ref)
    xt[...] = jnp.zeros_like(xt)
    rows_in.wait()
    tile = xt.shape[0]
    slots = (gbuf, ubuf, dbuf)
    bounds = 2 + experts            # behind [layer, count, id_0 .. id_{E-1}]

    def compute(e, slot, copies):
        lo, hi = sc_ref[bounds + e], sc_ref[bounds + e + 1]
        for c in copies:
            c.wait()

        def rows(t, carry):
            base = lo + t * tile
            n_rows = jnp.minimum(tile, hi - base)

            def gather(r, c):
                xt[pl.ds(r, 1), :] = x_ref[pl.ds(tok_ref[base + r], 1), :]
                return c

            jax.lax.fori_loop(0, n_rows, gather, 0)
            yt[...] = _expert(xt[...].astype(gbuf.dtype), slot, slots, glu)

            def scatter(r, c):
                row = pl.ds(tok_ref[base + r], 1)
                o_ref[row, :] += yt[pl.ds(r, 1), :] * wt_ref[base + r]
                return c

            jax.lax.fori_loop(0, n_rows, scatter, 0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(hi - lo, tile), rows, 0)

    _walk(sc_ref, (wg_hbm, wu_hbm, wd_hbm), slots, sem, pieces, compute)
    rows_out = pltpu.make_async_copy(o_ref, o_hbm, io_sem.at[1])
    rows_out.start()
    rows_out.wait()


def _call(kernel, name: str, scalars, rows_in, rows_out, stacks,
          plan: MoEDecodePlan, scratch, vmem_rows: int, interpret: bool,
          piped: bool = True):
    """One ``pallas_call`` of a walk: ``scalars`` prefetched into SMEM,
    ``rows_in`` and the result whole in VMEM through the call's pipeline
    (``piped``) or left in HBM for the kernel to copy by hand, the three
    stacks left in HBM, two slots of a unit's three matrices and
    ``scratch`` beside them."""
    wg, wu, wd = stacks
    h = wg.shape[2]

    def rows(a):
        return (pl.BlockSpec(a.shape, lambda *_: (0, 0)) if piped
                else pl.BlockSpec(memory_space=pl.ANY))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[rows(a) for a in rows_in]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=rows(rows_out),
            scratch_shapes=[
                pltpu.VMEM((2, h, plan.ip), wg.dtype),
                pltpu.VMEM((2, h, plan.ip), wu.dtype),
                pltpu.VMEM((2, plan.ip, h), wd.dtype),
                *scratch,
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        out_shape=rows_out,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=MOE_WEIGHT_VMEM_BYTES + vmem_rows),
        name=name,
        interpret=interpret,
    )(*scalars, *rows_in, wg, wu, wd)


def _checked_plan(n: int, h: int, wg) -> Tuple[MoEDecodePlan, int]:
    """The plan of a call of ``n`` rows and the VMEM its rows need."""
    plan = moe_decode_plan(h, wg.shape[3], wg.dtype)
    vmem_rows = plan and rows_vmem_bytes(n, h, wg.shape[1], plan, wg.dtype)
    if plan is None or n > MOE_WALK_MAX_ROWS \
            or vmem_rows > MOE_ROWS_VMEM_BYTES:
        raise ValueError(
            f"moe expert walk: {n} rows over experts of {h} x {wg.shape[3]} "
            f"{wg.dtype} (moe_decode.declined says what the kernel takes)")
    return plan, vmem_rows


def moe_decode_experts(x: jnp.ndarray, combine: jnp.ndarray,
                       wg: jnp.ndarray, wu: jnp.ndarray, wd: jnp.ndarray,
                       layer, *, glu: Callable, interpret: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The combine-weighted expert sum of a few tokens over the touched
    experts of one layer of a stack: at most :data:`ROW_TILE` rows, all of
    them against every touched expert.

    x (N, H) the step's tokens; combine (N, E) float32 over the experts the
    leaves hold (``moe.held_combine``); wg / wu (L, E, H, I), wd
    (L, E, I, H) the STACKED leaves and ``layer`` the layer's index (a
    Python int or a traced scalar); ``glu(gate, up)`` the gated
    nonlinearity on float32. Returns the float32 (N, H) sum and the number
    of experts read (the touched list's length)."""
    n, h = x.shape
    plan, vmem_rows = _checked_plan(n, h, wg)
    ids, count = touched_experts(combine)
    # rows in whole sublane tiles of the operands' dtype; a pad row is zero
    # and weighs nothing
    rows = -n % (32 // jnp.dtype(wg.dtype).itemsize)
    x, combine = (jnp.pad(a, ((0, rows), (0, 0)))
                  for a in (x.astype(wg.dtype), combine))
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), count.reshape(1), ids])
    out = _call(
        functools.partial(_kernel, pieces=plan.pieces, glu=glu),
        "moe_decode_experts", (scalars,), (x, combine),
        jax.ShapeDtypeStruct((n + rows, h), jnp.float32), (wg, wu, wd), plan,
        (), vmem_rows, interpret)
    return out[:n], count


def moe_chunk_experts(x: jnp.ndarray, token: jnp.ndarray,
                      weight: jnp.ndarray, group_sizes: jnp.ndarray,
                      wg: jnp.ndarray, wu: jnp.ndarray, wd: jnp.ndarray,
                      layer, *, glu: Callable, interpret: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The same sum for a step of MORE rows than a tile (a one-row prefill
    chunk), whose held experts each get few of them: every touched expert
    is streamed once and multiplied by ITS rows only, a tile at a time.

    x (N, H); the step's assignments to held experts SORTED BY EXPERT:
    ``token`` (A,) int32 the row of ``x``, ``weight`` (A,) float32 its
    combine weight, ``group_sizes`` (E,) how many fell to each expert
    (entries of the two lists past their sum are never read); the leaves,
    ``layer``, ``glu`` and the result as :func:`moe_decode_experts`."""
    n, h = x.shape
    plan, vmem_rows = _checked_plan(n, h, wg)
    group_sizes = group_sizes.astype(jnp.int32)
    ids, count = touched_experts(group_sizes[None, :])
    rows = -n % 8
    # float32 rows: one row is one sublane, read and written alone
    x = jnp.pad(x.astype(jnp.float32), ((0, rows), (0, 0)))
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), count.reshape(1), ids,
        jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)])
    tile = (ROW_TILE, h)
    out = _call(
        functools.partial(_rows_kernel, pieces=plan.pieces, glu=glu,
                          experts=wg.shape[1]),
        "moe_chunk_experts",
        (scalars, token.astype(jnp.int32), weight.astype(jnp.float32)), (x,),
        jax.ShapeDtypeStruct(x.shape, jnp.float32), (wg, wu, wd), plan,
        (pltpu.VMEM(tile, jnp.float32), pltpu.VMEM(tile, jnp.float32),
         pltpu.VMEM(x.shape, jnp.float32), pltpu.VMEM(x.shape, jnp.float32),
         pltpu.SemaphoreType.DMA((2,))),
        vmem_rows, interpret, piped=False)
    return out[:n], count


# ---------------------------------------------------------------------------
# The walk over units of ANY number of matrices. A PLAIN expert is two,
# ``act(x W_up) W_down`` (Nemotron-H's ReLU² experts): no gate leaf, one
# product a unit before the nonlinearity. Everything above this line is the
# gated form's, three matrices spelt out, and stays where it is: a Pallas
# kernel's serialised body holds its call stack's line numbers, so moving
# those lines rebuilds every gated expert cell's programs (ROADMAP trap 3).
# The functions below take the matrices as a list - the input projections,
# pieced by columns, then the output projection, pieced by rows - and serve
# both forms; the gated call sites move onto them with the next change that
# rebuilds those cells anyway.
# ---------------------------------------------------------------------------

#: activations of a plain expert the walk computes, on the float32 product
#: inside the kernel
PLAIN_WALK_ACTS = ("relu2",)


def matrices_of(moe) -> int:
    """Matrices an expert of ``moe`` holds: gate, up and down, or a plain
    expert's up and down."""
    return 2 if moe.glu_style == "plain" else 3


def walks_glu(moe) -> bool:
    """The kernel computes ``moe``'s nonlinearity (:func:`declined` names
    what it does not)."""
    if moe.glu_style == "plain":
        return moe.act in PLAIN_WALK_ACTS
    return moe.glu_style == "gated" and moe.act in WALK_ACTS


def _walk_units(sc_ref, stacks, slots, sem, pieces: int, compute: Callable):
    """:func:`_walk` over units of ``len(stacks)`` matrices: the last is
    pieced by rows, the others by columns."""
    layer = sc_ref[0]
    n_units = sc_ref[1] * pieces
    ip = slots[-1].shape[1]

    def copies(u, slot):
        e = sc_ref[2 + jax.lax.div(u, pieces)]
        if pieces == 1:
            srcs = [w.at[layer, e] for w in stacks]
        else:
            cols = pl.ds(pl.multiple_of(jax.lax.rem(u, pieces) * ip, LANES),
                         ip)
            srcs = [w.at[layer, e, :, cols] for w in stacks[:-1]]
            srcs.append(stacks[-1].at[layer, e, cols, :])
        return tuple(
            pltpu.make_async_copy(src, buf.at[slot], sem.at[k, slot])
            for k, (src, buf) in enumerate(zip(srcs, slots)))

    def start(u, slot):
        for c in copies(u, slot):
            c.start()

    @pl.when(n_units > 0)
    def _first():
        start(0, 0)

    def unit(u, carry):
        slot = jax.lax.rem(u, 2)

        @pl.when(u + 1 < n_units)
        def _next():
            start(u + 1, 1 - slot)

        compute(sc_ref[2 + jax.lax.div(u, pieces)], slot, copies(u, slot))
        return carry

    jax.lax.fori_loop(0, n_units, unit, 0)


def _unit(x, slot, slots, nonlin: Callable, copies=()):
    """``nonlin(x W_in ...) W_out`` of the rows ``x`` on the unit in
    ``slot``, in float32 (:func:`_expert` for any number of input
    projections)."""
    def arrived(k):
        if copies:
            copies[k].wait()

    products = []
    for k, buf in enumerate(slots[:-1]):
        arrived(k)
        products.append(jnp.dot(x, buf[slot],
                                preferred_element_type=jnp.float32))
    inter = nonlin(*products).astype(x.dtype)
    arrived(len(slots) - 1)
    return jnp.dot(inter, slots[-1][slot], preferred_element_type=jnp.float32)


def _tile_kernel(sc_ref, x_ref, comb_ref, *refs, matrices: int, pieces: int,
                 nonlin: Callable):
    """:func:`_kernel` (a step of one tile of rows, ALL against every unit)
    for units of ``matrices``: ``refs`` are the stacks, the result, the
    slots and the copies' semaphores."""
    stacks, o_ref = refs[:matrices], refs[matrices]
    slots, sem = refs[matrices + 1:2 * matrices + 1], refs[-1]
    o_ref[...] = jnp.zeros_like(o_ref)
    x = x_ref[...]
    lane_expert = jax.lax.broadcasted_iota(jnp.int32, comb_ref.shape, 1)

    def compute(e, slot, copies):
        out = _unit(x, slot, slots, nonlin, copies)
        w = jnp.sum(jnp.where(lane_expert == e, comb_ref[...], 0.0),
                    axis=1, keepdims=True)
        o_ref[...] += out * w

    _walk_units(sc_ref, stacks, slots, sem, pieces, compute)


def _group_rows_kernel(sc_ref, tok_ref, wt_ref, x_hbm, *refs, matrices: int,
                       pieces: int, nonlin: Callable, experts: int):
    """:func:`_rows_kernel` (a step of more rows than a tile, each unit
    against ITS rows) for units of ``matrices``: ``refs`` are the stacks, the
    result in HBM, the slots, then ``xt, yt, x_ref, o_ref, io_sem, sem``."""
    stacks, o_hbm = refs[:matrices], refs[matrices]
    slots = refs[matrices + 1:2 * matrices + 1]
    xt, yt, x_ref, o_ref, io_sem, sem = refs[2 * matrices + 1:]
    rows_in = pltpu.make_async_copy(x_hbm, x_ref, io_sem.at[0])
    rows_in.start()
    o_ref[...] = jnp.zeros_like(o_ref)
    xt[...] = jnp.zeros_like(xt)
    rows_in.wait()
    tile = xt.shape[0]
    bounds = 2 + experts            # behind [layer, count, id_0 .. id_{E-1}]

    def compute(e, slot, copies):
        lo, hi = sc_ref[bounds + e], sc_ref[bounds + e + 1]
        for c in copies:
            c.wait()

        def rows(t, carry):
            base = lo + t * tile
            n_rows = jnp.minimum(tile, hi - base)

            def gather(r, c):
                xt[pl.ds(r, 1), :] = x_ref[pl.ds(tok_ref[base + r], 1), :]
                return c

            jax.lax.fori_loop(0, n_rows, gather, 0)
            yt[...] = _unit(xt[...].astype(slots[0].dtype), slot, slots,
                            nonlin)

            def scatter(r, c):
                row = pl.ds(tok_ref[base + r], 1)
                o_ref[row, :] += yt[pl.ds(r, 1), :] * wt_ref[base + r]
                return c

            jax.lax.fori_loop(0, n_rows, scatter, 0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(hi - lo, tile), rows, 0)

    _walk_units(sc_ref, stacks, slots, sem, pieces, compute)
    rows_out = pltpu.make_async_copy(o_ref, o_hbm, io_sem.at[1])
    rows_out.start()
    rows_out.wait()


def _call_units(kernel, name: str, scalars, rows_in, rows_out, stacks,
                plan: MoEDecodePlan, scratch, vmem_rows: int,
                interpret: bool, piped: bool = True):
    """:func:`_call` for units of ``len(stacks)`` matrices."""
    h = stacks[-1].shape[3]

    def rows(a):
        return (pl.BlockSpec(a.shape, lambda *_: (0, 0)) if piped
                else pl.BlockSpec(memory_space=pl.ANY))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[rows(a) for a in rows_in]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(stacks),
            out_specs=rows(rows_out),
            scratch_shapes=[
                *(pltpu.VMEM((2, h, plan.ip), w.dtype) for w in stacks[:-1]),
                pltpu.VMEM((2, plan.ip, h), stacks[-1].dtype),
                *scratch,
                pltpu.SemaphoreType.DMA((len(stacks), 2)),
            ],
        ),
        out_shape=rows_out,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=MOE_WEIGHT_VMEM_BYTES + vmem_rows),
        name=name,
        interpret=interpret,
    )(*scalars, *rows_in, *stacks)


def _checked_units_plan(n: int, stacks) -> Tuple[MoEDecodePlan, int]:
    """:func:`_checked_plan` for units of ``len(stacks)`` matrices, the
    output projection ``stacks[-1]`` (L, E, I, H) last."""
    wd = stacks[-1]
    i, h = wd.shape[2:]
    plan = moe_decode_plan(h, i, wd.dtype, len(stacks))
    vmem_rows = plan and rows_vmem_bytes(n, h, wd.shape[1], plan, wd.dtype)
    if plan is None or n > MOE_WALK_MAX_ROWS \
            or vmem_rows > MOE_ROWS_VMEM_BYTES:
        raise ValueError(
            f"moe expert walk: {n} rows over experts of {len(stacks)} "
            f"matrices of {h} x {i} {wd.dtype} (moe_decode.declined says "
            "what the kernel takes)")
    return plan, vmem_rows


def moe_decode_units(x: jnp.ndarray, combine: jnp.ndarray, stacks, layer, *,
                     nonlin: Callable, interpret: bool = False
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`moe_decode_experts` over experts of ``len(stacks)`` matrices:
    ``stacks`` the input projections (L, E, H, I) then the output projection
    (L, E, I, H); ``nonlin`` takes the float32 products of the rows with a
    unit's input projections, in that order (a plain expert: the one)."""
    n, h = x.shape
    wd = stacks[-1]
    plan, vmem_rows = _checked_units_plan(n, stacks)
    ids, count = touched_experts(combine)
    rows = -n % (32 // jnp.dtype(wd.dtype).itemsize)
    x, combine = (jnp.pad(a, ((0, rows), (0, 0)))
                  for a in (x.astype(wd.dtype), combine))
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), count.reshape(1), ids])
    out = _call_units(
        functools.partial(_tile_kernel, matrices=len(stacks),
                          pieces=plan.pieces, nonlin=nonlin),
        "moe_decode_experts", (scalars,), (x, combine),
        jax.ShapeDtypeStruct((n + rows, h), jnp.float32), tuple(stacks),
        plan, (), vmem_rows, interpret)
    return out[:n], count


def moe_chunk_units(x: jnp.ndarray, token: jnp.ndarray, weight: jnp.ndarray,
                    group_sizes: jnp.ndarray, stacks, layer, *,
                    nonlin: Callable, interpret: bool = False
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`moe_chunk_experts` over experts of ``len(stacks)`` matrices
    (:func:`moe_decode_units` has ``stacks`` and ``nonlin``)."""
    n, h = x.shape
    plan, vmem_rows = _checked_units_plan(n, stacks)
    group_sizes = group_sizes.astype(jnp.int32)
    ids, count = touched_experts(group_sizes[None, :])
    rows = -n % 8
    x = jnp.pad(x.astype(jnp.float32), ((0, rows), (0, 0)))
    scalars = jnp.concatenate([
        jnp.asarray(layer, jnp.int32).reshape(1), count.reshape(1), ids,
        jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)])
    tile = (ROW_TILE, h)
    out = _call_units(
        functools.partial(_group_rows_kernel, matrices=len(stacks),
                          pieces=plan.pieces, nonlin=nonlin,
                          experts=stacks[-1].shape[1]),
        "moe_chunk_experts",
        (scalars, token.astype(jnp.int32), weight.astype(jnp.float32)), (x,),
        jax.ShapeDtypeStruct(x.shape, jnp.float32), tuple(stacks), plan,
        (pltpu.VMEM(tile, jnp.float32), pltpu.VMEM(tile, jnp.float32),
         pltpu.VMEM(x.shape, jnp.float32), pltpu.VMEM(x.shape, jnp.float32),
         pltpu.SemaphoreType.DMA((2,))),
        vmem_rows, interpret, piped=False)
    return out[:n], count
