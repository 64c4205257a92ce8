"""The Mamba-2 (SSD) one-token rule on the state walk of
``ops/delta_state_step.py``: one token of the selective state-space
recurrence for every row and head of ONE layer, in place on the stacked state
``(Ls, slots, nh, head_dim, d_state)`` float32 (reference: the T == 1 branch
of ``modules/ssm.py`` ``mamba2_mixer``, which stays as the declined path and
as the tests' reference).

As XLA fusions a decode step crossed a layer's state three times: a loop
fusion that read ``S0``, formed ``a S0 + (x dt) B^T`` in registers and reduced
it against ``C`` for ``y``, then ``add_dynamic-update-slice_fusion``, which
read ``S0`` AGAIN, formed the same ``S`` and wrote it through the
dynamic-update-slice (PERF.md section 6, PR 45). Here the walk
(:func:`delta_state_step.walk_state_blocks`: the grid, the index maps, dead
rows, the aliasing, the layer as a prefetched scalar) reads each live row's
tiles once and writes them once, and ``y`` is read off the NEW state while it
is in VMEM. The two rules share the walk and nothing else.

On one block of ``heads`` tiles ``(head_dim, d_state)``, ``head_dim`` on
sublanes and ``d_state`` on lanes, float32 throughout::

    S0 <- keep ? S0 : 0;  S = exp(dt A) S0 + (x dt) B^T;  y = S C

* ``x dt`` arrives a row per head and the kernel transposes the block's rows
  ONCE, so that each becomes a column (``head_dim`` to the sublanes,
  broadcast along the lanes); the per-head decay ``exp(dt A)`` arrives as
  ONE row, a lane a head, negative where ``keep`` is false, and is broadcast
  down the sublanes so that a head's column is its decay. Nothing is laid
  out for the kernel in HBM: every operand is a reshape of what the mixer
  has.
* ``B`` and ``C`` arrive as rows per GROUP (``d_state`` on the lanes,
  broadcast down the sublanes): head ``j`` reads group ``j // (nh / g)`` by a
  static index, no ``repeat`` is materialised.
* the read-out ``y = S C`` is a LANE reduction (``d_state`` lies on the
  lanes), ``heads x head_dim`` sums of ``d_state`` lanes a block: it goes
  through the MXU, a tile at a time (:func:`_mamba_update`).
* ``D x`` stays outside (a row-sized XLA fusion), as do the convolution and
  its tails, ``dt``, the gated norm and the projections.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import delta_state_step as walk
from .delta_state_step import LANES, SUBLANES, StateStepPlan


#: the most heads a block. The rule's body is unrolled over them (a head's
#: columns are static lanes), so what a set-up pays to trace and lower it,
#: twice a run, grows with them faster than in proportion, while a block of
#: a mebibyte already moves the state as fast as a row whole: at granite's
#: ``(64, 128)`` tiles blocks of 64 / 32 / 16 / 8 heads ran at 631 / 631 /
#: 619 / 486 GB/s and took 2.2 / 1.1 / 0.7 / 0.6 s to the first call
#: (PERF.md section 6, PR 45)
BLOCK_HEADS = 32


def mamba_step_plan(heads: int, groups: int, head_dim: int, d_state: int
                    ) -> Optional[StateStepPlan]:
    """How the kernel walks a row's ``heads`` tiles of ``(head_dim,
    d_state)``: the most heads a block, up to :data:`BLOCK_HEADS`, that
    divide ``heads``, fit :data:`delta_state_step.STATE_BLOCK_BYTES` and are
    whole B/C groups (or a whole part of ONE group); chosen from the shapes
    and from nothing else. None: the tile is not whole ``(8, 128)`` vregs
    (the read-out contracts whole lanes), or the heads do not split into the
    groups."""
    if head_dim % SUBLANES or d_state % LANES or heads % groups:
        return None
    per_group = heads // groups
    fits = [hb for hb in range(1, min(heads, BLOCK_HEADS) + 1)
            if heads % hb == 0
            and (hb % per_group == 0 or per_group % hb == 0)
            and hb * head_dim * d_state * 4 <= walk.STATE_BLOCK_BYTES]
    return StateStepPlan(max(fits), head_dim, d_state) if fits else None


def declined(stack, rows: int, tokens: int, groups: int,
             state_slots=None) -> str:
    """Why a step of ``rows`` rows of ``tokens`` tokens over the state
    ``stack`` (Ls, slots, nh, head_dim, d_state) does not take the kernel
    ("" = it does): what the walk declines of any rule (the step's shape,
    the stored dtype, the ambient mesh), then this rule's tile. Whatever is
    named keeps ``mamba2_mixer``'s XLA branch."""
    why = walk.declined_walk(stack, rows, tokens, state_slots)
    if why:
        return why
    _, _, h, hd, n = stack.shape
    if mamba_step_plan(h, groups, hd, n) is None:
        return (f"{h} tiles of {hd}x{n} in {groups} groups are not whole "
                f"{SUBLANES}x{LANES} tiles under {walk.STATE_BLOCK_BYTES} "
                "bytes")
    return ""


def _by_block(a, heads: int, plan: StateStepPlan):
    """B or C as a block reads it: (B, nh // heads, gb, d_state), the rows
    of the block's gb groups. a (B, g, d_state) per GROUP. Blocks of whole
    groups are a reshape; where a group is several blocks, its row is
    repeated once a BLOCK (never a head)."""
    b, g, n = a.shape
    hb = plan.heads
    per_group = heads // g
    if hb < per_group:
        a = jnp.repeat(a, per_group // hb, axis=1)
    return a.reshape(b, heads // hb, -1, n)


def _mamba_update(per_group: int, s_ref, x_ref, a_ref, b_ref, c_ref, s_out,
                  y_ref):
    """The SSD rule on one block, a head at a time, unrolled (a head's
    columns are static lanes). ``x_ref`` (heads, head_dim) is ``x dt`` a row
    a head, transposed ONCE here so that a head's is a column; ``a_ref`` (1,
    heads) the decay a lane a head, broadcast down the sublanes so that a
    head's column is its decay all the way (Mosaic broadcasts a row down
    sublanes or a column along lanes, not one element both ways), NEGATIVE
    where the row starts from zeros; ``b_ref`` / ``c_ref`` the block's
    groups' rows, a head reads its group's. The read-out contracts the NEW
    tile's lanes against ``C`` on the MXU, which is otherwise idle (``C`` as
    the eight streamed rows, the tile as the held operand, float32 passes):
    ``y`` comes out a ROW, ``head_dim`` on the lanes, as the caller wants
    it. With it the kernel runs at the bytes a second of the same walk with
    no read-out at all; a lane reduction on the VPU/XLU (``jnp.sum``) ran at
    62 % of that, a fold-and-select butterfly at 31 % (PERF.md section 6,
    PR 45)."""
    hb, hd, n = s_ref.shape
    x_dt = x_ref[...].T                                   # (hd, hb)
    decay = jnp.broadcast_to(a_ref[...], (hd, hb))
    b_rows, c_rows = b_ref[...], c_ref[...]
    for i in range(hb):
        g = i // per_group
        a = decay[:, i:i + 1]
        s = (jnp.where(a >= 0.0, a * s_ref[i], 0.0)
             + x_dt[:, i:i + 1] * b_rows[g:g + 1])
        s_out[i] = s
        y_ref[i:i + 1, :] = jax.lax.dot_general(
            jnp.broadcast_to(c_rows[g:g + 1], (SUBLANES, n)), s,
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:1]       # (1, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_state_step(stack: jnp.ndarray, layer, x_dt, decay, bm, cm, keep,
                     live, *, interpret: bool = False
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the SSD recurrence for every row and head of layer
    ``layer`` of ``stack`` (Ls, B, nh, head_dim, d_state) float32, in place.
    x_dt (B, nh, head_dim) = ``x * dt``, decay (B, nh) = ``exp(dt A)``, bm
    and cm (B, g, d_state) per GROUP (head ``j`` reads group ``j // (nh /
    g)``), float32; keep (B,) bool: False starts the row from zeros; live
    (B,) bool: a dead row is skipped and its ``y`` is zero. Returns ``(y (B,
    nh, head_dim), stack)`` with ``y = S C`` of the NEW state (``D x`` is the
    caller's to add)."""
    _, b, h, hd, n = stack.shape
    plan = mamba_step_plan(h, bm.shape[1], hd, n)
    if plan is None or stack.dtype != jnp.float32:
        raise ValueError(
            f"no state-step kernel for {h} tiles of {hd}x{n} in "
            f"{bm.shape[1]} groups stored as {stack.dtype} "
            "(mamba_state_step.declined says what the kernel takes)")
    hb = plan.heads
    nb = h // hb
    # keep rides on the decay's sign: exp(dt A) is positive
    decay = jnp.where(keep[:, None], decay, -1.0).astype(jnp.float32)
    stack, y = walk.walk_state_blocks(
        functools.partial(_mamba_update, h // bm.shape[1]), stack, layer,
        live, [x_dt.reshape(b, nb, hb, hd), decay.reshape(b, nb, 1, hb),
               _by_block(bm, h, plan), _by_block(cm, h, plan)],
        [jax.ShapeDtypeStruct((b, nb, hb, hd), jnp.float32)], plan,
        name="mamba_state_step", interpret=interpret)
    return y.reshape(b, h, hd), stack
