"""The state-step kernel (ISSUE 44, ``ops/delta_state_step.py``): one token
of the gated delta rule for every row and head of ONE layer, in place on the
stacked state, held to ``modules/ssm.py`` ``_delta_step`` (the declined path
and the reference) in interpret mode at small sizes:

  * at both cells' tiles, ``(96, 192)`` and ``(128, 128)``, with as many key
    heads as value heads and with half of them, two head blocks a row, and a
    dead row, a reset row and a live row in ONE call: ``o`` and ``S`` to
    float32 tolerance, every OTHER layer of the stack and the dead row's slot
    bit for bit, the stack still float32; a tile the kernel declines is
    named by ``declined`` and refused by the call;
  * whatever rows are dead (none, all, the first, the last, runs of them),
    the walk names a block for each of their steps that moves nothing;
  * what the kernel declines, by name; and what it runs with at the two
    cells' shapes (a row's heads whole in one block).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.modules import ssm
from neuronx_distributed_inference_tpu.ops import delta_state_step as dss

LAYERS, LAYER = 3, 1


def _inputs(rows, heads, key_heads, d_k, d_v, seed=44):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    return dict(
        stack=jax.random.normal(ks[0], (LAYERS, rows, heads, d_k, d_v)),
        q=unit(jax.random.normal(ks[1], (rows, key_heads, d_k))) * d_k ** -.5,
        k=unit(jax.random.normal(ks[2], (rows, key_heads, d_k))),
        v=jax.random.normal(ks[3], (rows, heads, d_v)),
        g=-jax.nn.softplus(jax.random.normal(ks[4], (rows, heads))),
        beta=2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (rows, heads))))


def _both(x, live, keep):
    """(o, stack) of the kernel and of ``_delta_step`` on layer LAYER, a
    dead row fed ``g = 0`` and ``beta = 0`` as the mixer feeds it."""
    live, keep = jnp.asarray(live), jnp.asarray(keep)
    g = jnp.where(live[:, None], x["g"], 0.0)
    beta = jnp.where(live[:, None], x["beta"], 0.0)
    got = jax.jit(lambda s: dss.delta_state_step(
        s, LAYER, x["q"], x["k"], x["v"], g, beta, keep, live,
        interpret=True))(x["stack"])
    group = x["v"].shape[1] // x["q"].shape[1]
    rep = lambda a: jnp.repeat(a, group, axis=1)             # noqa: E731
    st0 = jnp.where(keep[:, None, None, None], x["stack"][LAYER], 0.0)
    return got, ssm._delta_step(rep(x["q"]), rep(x["k"]), x["v"], g, beta,
                                st0)


def _hold(x, live, keep):
    (o, stack), (o_ref, s_ref) = _both(x, live, keep)
    live = np.asarray(live)
    before = np.asarray(x["stack"])
    o, stack = np.asarray(o), np.asarray(stack)
    assert stack.dtype == np.float32 and stack.shape == before.shape
    np.testing.assert_allclose(o[live], np.asarray(o_ref)[live], atol=2e-6,
                               rtol=1e-5)
    np.testing.assert_allclose(stack[LAYER][live], np.asarray(s_ref)[live],
                               atol=2e-6, rtol=1e-5)
    # a dead row: its slot bit for bit, its o zero; every other layer too
    assert (stack[LAYER][~live] == before[LAYER][~live]).all()
    assert not o[~live].any()
    for other in set(range(LAYERS)) - {LAYER}:
        assert (stack[other] == before[other]).all()


@pytest.mark.parametrize("tile, heads, key_heads, blocks", [
    ((96, 192), 20, 20, 2), ((96, 192), 20, 10, 2),
    ((128, 128), 32, 32, 2), ((128, 128), 32, 16, 2),
    ((12, 20), 4, 4, None), ((12, 20), 4, 2, None)])
def test_one_token_in_place_against_the_xla_step(tile, heads, key_heads,
                                                 blocks, monkeypatch):
    # a budget that cuts these rows in two blocks (the cells' rows fit the
    # real one whole, and nothing else in the suite has these shapes: the
    # kernel's jit cache is keyed by them)
    monkeypatch.setattr(dss, "STATE_BLOCK_BYTES", 1 << 20)
    # row 0 dead, row 1 reset (keep false), row 2 live, row 3 dead
    live, keep = [False, True, True, False], [True, False, True, True]
    x = _inputs(4, heads, key_heads, *tile)
    spec = ssm.SSMSpec(kind="gated_delta", d_inner=heads * tile[1],
                       num_heads=heads, head_dim=tile[1], d_state=tile[0],
                       num_key_heads=key_heads)
    why = ssm.state_kernel_declined(spec, x["stack"], 4, 1)
    if blocks is None:
        assert why == (f"{heads // key_heads} tiles of 12x20 a key head are "
                       "not whole 8x64 tiles under 1048576 bytes")
        with pytest.raises(ValueError, match="no state-step kernel"):
            _both(x, live, keep)
        return
    assert why == ""
    plan = dss.state_step_plan(heads, key_heads, *tile)
    assert heads // plan.heads == blocks
    assert plan.heads % (heads // key_heads) == 0      # whole key heads
    assert ssm.state_kernel_note(spec, x["stack"]) == \
        f"heads={plan.heads} tile={tile[0]}x{tile[1]}"
    _hold(x, live, keep)


@pytest.mark.parametrize("live", [
    [True] * 5, [False] * 5, [False, False, True, False, False],
    [True, False, False, True, False], [False, True, True, True, True]],
    ids=lambda live: "".join("L" if r else "d" for r in live))
def test_dead_rows_move_nothing(live):
    x = _inputs(5, 4, 2, 8, 64, seed=45)
    _hold(x, live, [True, True, False, True, True])
    # the scalars the walk prefetches: a dead row's steps all name ONE
    # block, which a live row's steps beside it name too
    mode, row, block = (np.asarray(a) for a in
                        dss._visits(jnp.asarray(live), 3))
    for r, alive in enumerate(live):
        if alive:
            assert (mode[r], row[r]) == (dss._LIVE, r)
        elif any(live):
            assert mode[r] == dss._DEAD and live[row[r]]
            assert block[r] == (2 if row[r] < r else 0)
            assert not any(live[min(r, row[r]) + 1:max(r, row[r])])
        else:
            assert (mode[r], row[r], block[r]) == (dss._CARRY, 0, 0)


@pytest.mark.parametrize("case, why", [
    (dict(tokens=8), "8 tokens a row: the chunked form, " + ssm.SOLVE_NOTE),
    (dict(state_slots=np.zeros((4,), np.int32)),
     "rows gathered from their slots"),
    (dict(rows=2), "rows gathered from their slots"),
    (dict(dtype=jnp.bfloat16), "state stored as bfloat16"),
    (dict(kind="rglru"), "no state-step kernel for kind rglru"),
    (dict(), "")])
def test_what_the_kernel_declines_is_named(case, why):
    spec = ssm.SSMSpec(kind=case.get("kind", "gated_delta"), d_inner=256,
                       num_heads=4, head_dim=64, d_state=8)
    stack = jax.ShapeDtypeStruct((3, 4, 4, 8, 64),
                                 case.get("dtype", jnp.float32))
    assert ssm.state_kernel_declined(
        spec, stack, case.get("rows", 4), case.get("tokens", 1),
        case.get("state_slots")) == why


@pytest.mark.parametrize("heads, key_heads, tile", [
    (30, 30, (96, 192)), (32, 16, (128, 128))])
def test_a_cells_row_is_one_block(heads, key_heads, tile):
    """At the two cells' shapes a row's heads fit one block, so the
    per-head operands reach the call as they are (no head axis split in
    front of it: 30 = 3 x 10 is not whole sublane tiles)."""
    plan = dss.state_step_plan(heads, key_heads, *tile)
    assert plan == dss.StateStepPlan(heads, *tile)
    assert heads * dss._tile_vmem_bytes(*tile) <= dss.STATE_BLOCK_BYTES
    x = jax.eval_shape(
        lambda: dss.delta_rows(
            jnp.zeros((2, key_heads, tile[0])),
            jnp.zeros((2, key_heads, tile[0])), jnp.zeros((2, heads)),
            jnp.zeros((2, heads)), jnp.ones((2,), bool), plan))
    assert x.shape == (2, 1, 2 * key_heads + heads + 2, tile[0])
