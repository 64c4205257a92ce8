"""Pallas state-step kernel: one token of a matrix-state recurrence for every
row and head of ONE layer, in place on the stacked state (reference: the
gated delta rule's one-token step, ``modules/ssm.py`` ``_delta_step``, which
stays as the declined path and as the tests' reference).

THREE rules share the walk of this file (:func:`walk_state_blocks`,
:func:`declined_walk`, :class:`StateStepPlan`) and nothing else: the gated
delta rule's with ONE decay a head, below (:func:`delta_state_step`), the
same rule with its decay BY CHANNEL (:func:`kda_state_step`, PR 67: the
decay rides as a column a head beside ``q`` and ``k``, and lands on the state
before it is read through ``k``), and Mamba-2's one-token SSD step
(``ops/mamba_state_step.py``, PR 45), each with its own operand packing; the
two delta rules share a plan and ``declined``. ``modules/ssm.py``
``state_kernel_declined`` picks the rule by the layer's kind.

A decode step of a gated delta-rule layer is bound by its state's bytes:
every live row's ``(H, d_k, d_v)`` float32 state is read and written once a
token, 2.2 MB a row a layer. As XLA fusions the step crossed the state three
times (a reduction pass for ``S0^T k`` and ``S0^T q`` BEFORE the pass that
reads ``S0`` again and writes ``S`` through a dynamic-update-slice, at under
half the chip's bandwidth: PERF.md section 6, PR 44). Here it crosses once
each way:

* HBM: the stack ``(Ls, slots, H, d_k, d_v)`` is the kernel's input AND its
  output (``input_output_aliases``): the block index map picks ``(layer,
  row, head block)``, and every block the walk does not visit keeps the
  donated buffer's own bytes. No ``arr[li]`` slice out, no
  ``arr.at[li].set`` back. The layer is a prefetched scalar, so the calls of
  a program's layers are ONE kernel, traced and lowered once (a static
  index made twelve, 1.4 s of host time in every set-up against 0.35:
  PERF.md section 6, PR 44).
* VMEM: a block is ``heads`` value heads' ``(d_k, d_v)`` tiles
  (:func:`state_step_plan`: the most whole key-head groups under
  :data:`STATE_BLOCK_BYTES`), loaded once, two in flight each way (the
  pipeline's double buffers). Beside it the block's key rows (below) and
  value rows.
* VPU, float32: ``S0 <- keep ? S0 : 0``, ``mem_k = S0^T k``, ``mem_q = S0^T
  q``, ``delta = beta (v - a mem_k)``, ``S = a S0 + k delta^T``, ``o = a
  mem_q + (k . q) delta``: ``_delta_step``'s algebra, the state never in
  another precision.
* SMEM (scalar prefetch): the layer, a row's mode and, for a dead row, the
  block its grid steps name instead of its own. A dead row (``valid``
  false) is neither read nor written: its steps name the block the walk
  visited last (or visits next), so the pipeline moves nothing for them,
  and its ``o`` is zero. Only when NO row is live (a warm-up's dummy
  dispatch) does the walk carry one block through unchanged.

A state tile has ``d_k`` on sublanes and ``d_v`` on lanes, so ``k`` and
``q`` must reach the VPU as COLUMNS (``d_k`` on sublanes, broadcast along
lanes). The caller hands them as they come out of the convolution, a row a
KEY head, and the kernel transposes a block's rows once (a few vregs
through the XLU): value head ``j`` reads key head ``j // group`` by a static
lane index, no ``repeat`` is materialised, and nothing is laid out for the
kernel in HBM. The per-head decay ``a`` and the row's ``keep`` ride as more
rows of the same small array, each along its whole row so that it too
becomes a column, and the write strength ``beta`` as a last row
(:func:`delta_rows`); ``k . q`` is computed on the columns.

:func:`walk_state_blocks` is the walk (grid, index maps, dead rows, the
aliasing) and takes the update rule as a function of one block's refs: the
other rules hand it ``_kda_update`` and ``_mamba_update``.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM one block of state tiles may take (a tile's lanes round up to whole
#: vregs there): the pipeline holds two each way. Both cells' rows fit whole
#: (30 heads of (96, 192) are 2.8 MiB, 32 of (128, 128) 2 MiB), so their
#: per-head operands are handed over as they are, no head axis split in
#: front of the call; blocks of 5, 10 and 30 heads ran at the same bytes a
#: second (PERF.md section 6, PR 44)
STATE_BLOCK_BYTES = 4 << 20
SUBLANES, LANES = 8, 128

# a row's mode, prefetched (SMEM)
_DEAD, _LIVE, _CARRY = 0, 1, 2


class StateStepPlan(NamedTuple):
    """What one call of the kernel runs with (:func:`state_step_plan`; the
    Mamba rule's ``mamba_step_plan``: ``heads`` a block of tiles ``(d_k,
    d_v)`` = (sublanes, lanes), its ``(head_dim, d_state)``)."""
    heads: int          # value heads a block
    d_k: int
    d_v: int

    def note(self) -> str:
        """The engagement record's text (``kernel_mode.note``)."""
        return f"heads={self.heads} tile={self.d_k}x{self.d_v}"


def _tile_vmem_bytes(d_k: int, d_v: int) -> int:
    return d_k * -(-d_v // LANES) * LANES * 4


def state_step_plan(heads: int, key_heads: int, d_k: int, d_v: int
                    ) -> Optional[StateStepPlan]:
    """How the kernel walks a row's ``heads`` tiles of ``(d_k, d_v)``: the
    most heads a block, in whole key-head groups and dividing ``heads``,
    that fit :data:`STATE_BLOCK_BYTES` (and a row of ``d_k`` lanes: a
    block's ``beta`` rides in one, :func:`delta_rows`); chosen from the
    shapes and from nothing else. None: the tile is not whole sublane
    tiles by half vregs, or one group's tiles do not fit."""
    if d_k % SUBLANES or d_v % (LANES // 2) or heads % key_heads:
        return None
    group = heads // key_heads
    fits = [hb for hb in range(group, min(heads, d_k) + 1, group)
            if heads % hb == 0
            and hb * _tile_vmem_bytes(d_k, d_v) <= STATE_BLOCK_BYTES]
    return StateStepPlan(max(fits), d_k, d_v) if fits else None


def declined_walk(stack, rows: int, tokens: int, state_slots=None) -> str:
    """Why a step of ``rows`` rows of ``tokens`` tokens over the state
    ``stack`` (Ls, slots, ...) does not take the walk ("" = it does),
    whatever the rule: read from what the call shows - the step's shape, the
    stack, the ambient mesh - and from nothing else."""
    if tokens != 1:
        return f"{tokens} tokens a row: the chunked form"
    if state_slots is not None or rows != stack.shape[1]:
        return "rows gathered from their slots"
    if stack.dtype != jnp.float32:
        return f"state stored as {stack.dtype}"
    mesh = jax.sharding.get_abstract_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    if wide:
        return "mesh axes wider than one: " + ",".join(wide)
    return ""


def declined(stack, rows: int, tokens: int, key_heads: int,
             state_slots=None) -> str:
    """Why a step of ``rows`` rows of ``tokens`` tokens over the delta-rule
    state ``stack`` (Ls, slots, H, d_k, d_v) does not take the kernel ("" =
    it does): what the walk declines of any rule (:func:`declined_walk`),
    then this rule's tile. Whatever is named here keeps ``_delta_step`` (one
    token) or the chunked form."""
    why = declined_walk(stack, rows, tokens, state_slots)
    if why:
        return why
    _, _, h, d_k, d_v = stack.shape
    if state_step_plan(h, key_heads, d_k, d_v) is None:
        return (f"{h // key_heads} tiles of {d_k}x{d_v} a key head "
                f"are not whole {SUBLANES}x{LANES // 2} tiles under "
                f"{STATE_BLOCK_BYTES} bytes")
    return ""


def _visits(live: jnp.ndarray, n_blocks: int):
    """(mode, row, block) a row, int32: the prefetched scalars of the walk.
    A live row visits its own blocks. A dead row's steps all name ONE block,
    the last of the nearest live row before it, else the first of the
    nearest after it: the block index does not change across them, so the
    pipeline neither fetches nor writes. With no live row at all every step
    names block 0 of row 0, which is carried through unchanged."""
    n = live.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    after = jax.lax.cummin(jnp.where(live, idx, n), reverse=True)
    any_live = after[0] < n
    row = jnp.where(live, idx, jnp.where(
        before >= 0, before, jnp.where(any_live, after, 0)))
    block = jnp.where(before >= 0, n_blocks - 1, 0)
    mode = jnp.where(live, _LIVE, jnp.where(any_live, _DEAD, _CARRY))
    return mode.astype(jnp.int32), row, block.astype(jnp.int32)


def _walk_kernel(update: Callable, n_in: int, layer_ref, mode_ref, row_ref,
                 block_ref, s_ref, *refs):
    del layer_ref, row_ref, block_ref    # the index maps read them
    ins, s_out, outs = refs[:n_in], refs[n_in], refs[n_in + 1:]
    mode = mode_ref[pl.program_id(0)]

    @pl.when(mode == _LIVE)
    def _live():
        update(s_ref, *ins, s_out, *outs)

    @pl.when(mode != _LIVE)
    def _dead():
        for o in outs:
            o[...] = jnp.zeros(o.shape, o.dtype)

    @pl.when(mode == _CARRY)
    def _carry():
        s_out[...] = s_ref[...]


def walk_state_blocks(update: Callable, stack: jnp.ndarray, layer,
                      live: jnp.ndarray, operands: Sequence[jnp.ndarray],
                      outs: Sequence[jax.ShapeDtypeStruct],
                      plan: StateStepPlan, *, name: str,
                      interpret: bool = False):
    """One pass over layer ``layer`` of ``stack`` (Ls, slots, H, d_k, d_v),
    in place: for every live row and every block of ``plan.heads`` heads,
    ``update(s_ref, *operand_refs, s_out_ref, *out_refs)`` with ``s_ref`` /
    ``s_out_ref`` the block's ``(heads, d_k, d_v)`` tiles before and after.
    ``operands`` and ``outs`` are shaped ``(slots, H // heads, ...)``: a
    block sees its own ``(...)`` of each. ``live`` (slots,) bool: a dead
    row's state is neither read nor written and its ``outs`` are zero.
    Returns ``(stack, *outs)``; the stack is the donated input's buffer."""
    _, n, h, d_k, d_v = stack.shape
    hb = plan.heads
    n_blocks = h // hb
    # the layer rides with the walk's scalars: every layer's call is then
    # the SAME kernel, traced and lowered once a program
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
               *_visits(live, n_blocks))

    def state_map(r, j, layer, mode, row, block):
        return (layer[0], row[r],
                jnp.where(mode[r] == _LIVE, j, block[r]), 0, 0)

    def own(a):
        rest = a.shape[2:]
        return pl.BlockSpec((None, None) + rest,
                            lambda r, j, *_: (r, j) + (0,) * len(rest))

    state_spec = pl.BlockSpec((None, None, hb, d_k, d_v), state_map)
    return pl.pallas_call(
        functools.partial(_walk_kernel, update, len(operands)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n, n_blocks),
            in_specs=[state_spec] + [own(a) for a in operands],
            out_specs=[state_spec] + [own(o) for o in outs],
        ),
        out_shape=[jax.ShapeDtypeStruct(stack.shape, stack.dtype), *outs],
        # the stack is operand len(scalars): its buffer is the first output
        input_output_aliases={len(scalars): 0},
        # sequential: a dead row's steps lean on the block before them;
        # VMEM: two state blocks each way, the small operands beside them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=5 * STATE_BLOCK_BYTES + (4 << 20)),
        name=name,
        interpret=interpret,
    )(*scalars, stack, *operands)


def delta_rows(q, k, a, beta, keep, plan: StateStepPlan):
    """The row operand of :func:`delta_state_step`: (B, H // heads, 2 kb +
    heads + 2, d_k) float32 with, a block, the rows ``[q of its kb key
    heads | k of its key heads | a of each value head, along the whole row |
    keep, along the whole row | beta, a value head a lane]``. q, k (B, KH,
    d_k) per KEY head; a, beta (B, H); keep (B,) bool."""
    b, kh, d_k = q.shape
    h = a.shape[1]
    hb = plan.heads
    nb, kb = h // hb, hb * kh // h
    along = lambda x, n: jnp.broadcast_to(                  # noqa: E731
        x.astype(jnp.float32), (b, nb, n, d_k))
    return jnp.concatenate(
        [q.reshape(b, nb, kb, d_k), k.reshape(b, nb, kb, d_k),
         along(a.reshape(b, nb, hb, 1), hb),
         along(keep[:, None, None, None], 1),
         jnp.pad(beta.reshape(b, nb, 1, hb),
                 ((0, 0),) * 3 + ((0, d_k - hb),))], axis=2)


def _delta_head(s0, qc, kc, a, beta, keep, v):
    """One head's tile: s0 (dk, dv), the columns qc, kc, a (dk, 1) and keep
    (dk, 1) bool, beta (1, 1), v (1, dv) -> (S (dk, dv), o (1, dv)):
    ``_delta_step``'s algebra."""
    s0 = jnp.where(keep, s0, 0.0)
    mem_k = jnp.sum(s0 * kc, axis=0, keepdims=True)               # (1, dv)
    mem_q = jnp.sum(s0 * qc, axis=0, keepdims=True)
    kq = jnp.sum(kc * qc, axis=0, keepdims=True)                  # (1, 1)
    delta = beta * (v - a[:1] * mem_k)
    return a * s0 + kc * delta, a[:1] * mem_q + kq * delta


def _delta_update(group: int, s_ref, x_ref, v_ref, s_out, o_ref):
    """The gated delta rule on one block, a head at a time, unrolled: a
    head's columns are static lanes (a ``fori_loop`` over the heads with the
    columns in a VMEM scratch ran 30 to 100 % slower: PERF.md section 6, PR
    44). ``x_ref`` is :func:`delta_rows`' block: all its rows but the last
    are turned into columns here (d_k to the sublanes, as a state tile has
    it: Mosaic broadcasts a column along the lanes, a row down the
    sublanes, and not a single element both ways); a value head reads its
    key head's."""
    hb = s_ref.shape[0]
    kb = hb // group
    n_cols = 2 * kb + hb + 1
    cols = x_ref[:n_cols, :].T                               # (dk, n_cols)
    col = lambda c: cols[:, c:c + 1]                         # noqa: E731
    keep = col(n_cols - 1) > 0.0
    for i in range(hb):
        s_out[i], o_ref[i:i + 1, :] = _delta_head(
            s_ref[i], col(i // group), col(kb + i // group),
            col(2 * kb + i), x_ref[n_cols:, i:i + 1], keep,
            v_ref[i:i + 1, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_state_step(stack: jnp.ndarray, layer, q, k, v, g, beta,
                     keep, live, *, interpret: bool = False
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the gated delta rule for every row and head of layer
    ``layer`` of ``stack`` (Ls, B, H, d_k, d_v) float32, in place. q, k
    (B, KH, d_k) per KEY head (value head ``j`` reads key head ``j // (H /
    KH)``), v (B, H, d_v), g (log decay) and beta (B, H), float32; keep
    (B,) bool: False starts the row from zeros; live (B,) bool: a dead row
    is skipped and its ``o`` is zero. Returns ``(o (B, H, d_v), stack)``:
    for a live row what ``_delta_step`` returns."""
    _, b, h, d_k, d_v = stack.shape
    plan = state_step_plan(h, q.shape[1], d_k, d_v)
    if plan is None or stack.dtype != jnp.float32:
        raise ValueError(
            f"no state-step kernel for {h} tiles of {d_k}x{d_v} over "
            f"{q.shape[1]} key heads stored as {stack.dtype} "
            "(delta_state_step.declined says what the kernel takes)")
    hb = plan.heads
    rows = (b, h // hb, hb, d_v)
    stack, o = walk_state_blocks(
        functools.partial(_delta_update, h // q.shape[1]), stack, layer,
        live, [delta_rows(q, k, jnp.exp(g), beta, keep, plan),
               v.reshape(rows)],
        [jax.ShapeDtypeStruct(rows, jnp.float32)], plan,
        name="delta_state_step", interpret=interpret)
    return o.reshape(b, h, d_v), stack


def kda_rows(q, k, a, beta, keep, plan: StateStepPlan):
    """The row operand of :func:`kda_state_step`: (B, H // heads, 3 heads +
    2, d_k) float32 with, a block, the rows ``[q of its heads | k of its
    heads | a of its heads, a channel a lane | keep, along the whole row |
    beta, a head a lane]``. q, k, a (B, H, d_k); beta (B, H); keep (B,)."""
    b, h, d_k = q.shape
    hb = plan.heads
    nb = h // hb
    return jnp.concatenate(
        [x.astype(jnp.float32).reshape(b, nb, hb, d_k) for x in (q, k, a)]
        + [jnp.broadcast_to(keep.astype(jnp.float32)[:, None, None, None],
                            (b, nb, 1, d_k)),
           jnp.pad(beta.reshape(b, nb, 1, hb),
                   ((0, 0),) * 3 + ((0, d_k - hb),))], axis=2)


def _kda_head(s0, qc, kc, ac, beta, keep, v):
    """One head's tile under a decay by channel: s0 (dk, dv), the columns
    qc, kc, ac (dk, 1) and keep (dk, 1) bool, beta (1, 1), v (1, dv) -> (S
    (dk, dv), o (1, dv)): ``modules/ssm.py`` ``_kda_step``'s algebra, the
    decayed state formed once and read through both columns."""
    s1 = jnp.where(keep, s0, 0.0) * ac
    mem_k = jnp.sum(s1 * kc, axis=0, keepdims=True)               # (1, dv)
    mem_q = jnp.sum(s1 * qc, axis=0, keepdims=True)
    kq = jnp.sum(kc * qc, axis=0, keepdims=True)                  # (1, 1)
    delta = beta * (v - mem_k)
    return s1 + kc * delta, mem_q + kq * delta


def _kda_update(s_ref, x_ref, v_ref, s_out, o_ref):
    """The delta rule gated by channel on one block, a head at a time,
    unrolled as :func:`_delta_update` is. ``x_ref`` is :func:`kda_rows`'
    block: all its rows but the last become columns here."""
    hb = s_ref.shape[0]
    n_cols = 3 * hb + 1
    cols = x_ref[:n_cols, :].T                               # (dk, n_cols)
    col = lambda c: cols[:, c:c + 1]                         # noqa: E731
    keep = col(n_cols - 1) > 0.0
    for i in range(hb):
        s_out[i], o_ref[i:i + 1, :] = _kda_head(
            s_ref[i], col(i), col(hb + i), col(2 * hb + i),
            x_ref[n_cols:, i:i + 1], keep, v_ref[i:i + 1, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_state_step(stack: jnp.ndarray, layer, q, k, v, g, beta, keep, live,
                   *, interpret: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the delta rule gated by channel for every row and head
    of layer ``layer`` of ``stack`` (Ls, B, H, d_k, d_v) float32, in place.
    q, k and g (the log decay, by channel) (B, H, d_k), v (B, H, d_v), beta
    (B, H), float32; ``keep`` / ``live`` as :func:`delta_state_step`'s.
    Returns ``(o (B, H, d_v), stack)``: for a live row what ``_kda_step``
    returns."""
    _, b, h, d_k, d_v = stack.shape
    plan = state_step_plan(h, h, d_k, d_v)
    if plan is None or stack.dtype != jnp.float32:
        raise ValueError(
            f"no state-step kernel for {h} tiles of {d_k}x{d_v} stored as "
            f"{stack.dtype} (delta_state_step.declined says what the kernel "
            "takes)")
    hb = plan.heads
    rows = (b, h // hb, hb, d_v)
    stack, o = walk_state_blocks(
        _kda_update, stack, layer, live,
        [kda_rows(q, k, jnp.exp(g), beta, keep, plan), v.reshape(rows)],
        [jax.ShapeDtypeStruct(rows, jnp.float32)], plan,
        name="kda_state_step", interpret=interpret)
    return o.reshape(b, h, d_v), stack
