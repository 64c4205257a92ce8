"""The Mamba-2 rule of the state-step walk (ISSUE 45,
``ops/mamba_state_step.py``): one token of the SSD recurrence for every row
and head of ONE layer, in place on the stacked state, held to the T == 1 XLA
branch of ``modules/ssm.py`` ``mamba2_mixer`` (the declined path and the
reference) in interpret mode at small sizes:

  * the kernel against the branch's algebra at granite's tile ``(64, 128)``
    with 64 heads in one group (a row is two blocks of 32), at a multi-group
    shape
    and at shapes a smaller budget cuts into several blocks a row (whole
    groups a block, and parts of one group a block), with a dead row, a
    reset row and a live row in ONE call: ``y`` and ``S`` to float32
    tolerance, every OTHER layer of the stack and the dead row's slot bit for
    bit, its ``y`` zero, the stack still float32;
  * ``mamba2_mixer`` handed the stack (``ssm.StateStack``) against the same
    mixer handed its layer's rows: the block's output, the conv tails and
    the state;
  * what the rule declines, by name, and what it runs with at granite's
    shape; its plan does not inherit the delta rule's cap on a block's heads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.modules import ssm
from neuronx_distributed_inference_tpu.ops import delta_state_step as dss
from neuronx_distributed_inference_tpu.ops import mamba_state_step as mss

LAYERS, LAYER = 3, 1


def _inputs(rows, heads, groups, head_dim, d_state, seed=45):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        stack=jax.random.normal(ks[0],
                                (LAYERS, rows, heads, head_dim, d_state)),
        x_dt=0.1 * jax.random.normal(ks[1], (rows, heads, head_dim)),
        decay=jnp.exp(-jax.nn.softplus(jax.random.normal(ks[2],
                                                         (rows, heads)))),
        bm=jax.random.normal(ks[3], (rows, groups, d_state)),
        cm=jax.random.normal(ks[4], (rows, groups, d_state)))


def _xla_step(x, keep):
    """``mamba2_mixer``'s T == 1 branch on layer LAYER: ``(y, S)``."""
    b, h, hd = x["x_dt"].shape
    g = x["bm"].shape[1]
    st0 = jnp.where(keep[:, None, None, None], x["stack"][LAYER],
                    0.0).reshape(b, g, h // g, hd, -1)
    dbx = (x["x_dt"].reshape(b, g, h // g, hd)[..., None]
           * x["bm"][:, :, None, None, :])
    st = st0 * x["decay"].reshape(b, g, h // g)[..., None, None] + dbx
    y = jnp.einsum("bgrdn,bgn->bgrd", st, x["cm"],
                   precision=jax.lax.Precision.HIGHEST)
    return y.reshape(b, h, hd), st.reshape(x["stack"].shape[1:])


def _hold(x, live, keep):
    live, keep = jnp.asarray(live), jnp.asarray(keep)
    y, stack = jax.jit(lambda s: mss.mamba_state_step(
        s, LAYER, x["x_dt"], x["decay"], x["bm"], x["cm"], keep, live,
        interpret=True))(x["stack"])
    y_ref, s_ref = _xla_step(x, keep)
    live = np.asarray(live)
    before = np.asarray(x["stack"])
    y, stack = np.asarray(y), np.asarray(stack)
    assert stack.dtype == np.float32 and stack.shape == before.shape
    np.testing.assert_allclose(y[live], np.asarray(y_ref)[live], atol=2e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(stack[LAYER][live], np.asarray(s_ref)[live],
                               atol=2e-6, rtol=1e-5)
    # a dead row: its slot bit for bit, its y zero; every other layer too
    assert (stack[LAYER][~live] == before[LAYER][~live]).all()
    assert not y[~live].any()
    for other in set(range(LAYERS)) - {LAYER}:
        assert (stack[other] == before[other]).all()


@pytest.mark.parametrize("tile, heads, groups, budget, blocks", [
    ((64, 128), 64, 1, None, 2),          # granite: two blocks of 32 a row
    ((16, 128), 8, 4, None, 1),           # four groups in one block
    ((16, 128), 8, 2, 1 << 15, 2),        # a group a block
    ((16, 128), 8, 1, 1 << 14, 4),        # quarters of ONE group
    ((8, 256), 6, 3, 1 << 14, 3)],
    ids=["granite", "groups", "group-blocks", "part-blocks", "two-vregs"])
def test_one_token_in_place_against_the_xla_branch(tile, heads, groups,
                                                   budget, blocks,
                                                   monkeypatch):
    if budget is not None:
        monkeypatch.setattr(dss, "STATE_BLOCK_BYTES", budget)
    # row 0 dead, row 1 reset (keep false), row 2 live, row 3 dead
    live, keep = [False, True, True, False], [True, False, True, True]
    x = _inputs(4, heads, groups, *tile)
    spec = ssm.SSMSpec(kind="mamba2", d_inner=heads * tile[0],
                       num_heads=heads, head_dim=tile[0], d_state=tile[1],
                       n_groups=groups)
    assert ssm.state_kernel_declined(spec, x["stack"], 4, 1) == ""
    plan = mss.mamba_step_plan(heads, groups, *tile)
    assert heads // plan.heads == blocks
    per_group = heads // groups
    assert plan.heads % per_group == 0 or per_group % plan.heads == 0
    # (the record names the B / C groups where there are several: ISSUE 64)
    assert ssm.state_kernel_note(spec, x["stack"]) == \
        f"heads={plan.heads} tile={tile[0]}x{tile[1]}" \
        + (f" groups={groups}" if groups > 1 else "")
    _hold(x, live, keep)


def test_no_live_row_moves_nothing():
    x = _inputs(3, 4, 2, 8, 128, seed=46)
    _hold(x, [False] * 3, [True, False, True])


def _mixer_operands(spec, hidden, rows, seed=47):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    lw = {}
    for name, ps in ssm.ssm_param_specs(spec, hidden, 1, jnp.float32).items():
        a = jax.random.normal(next(ks), ps.shape[1:], jnp.float32)
        lw[name] = a * (0.2 if a.ndim > 1 else 1.0)
    state = {k: jax.random.normal(next(ks), (LAYERS, rows) + shape[2:], dt)
             for k, (shape, dt) in ssm.ssm_state_shapes(
                 spec, LAYERS, rows, jnp.float32).items()}
    return lw, state, jax.random.normal(next(ks), (rows, 1, hidden))


@pytest.mark.parametrize("gated_norm", [True, False])
def test_the_mixer_handed_the_stack_is_the_mixer_handed_its_rows(gated_norm):
    """``mamba2_mixer`` with ``state["ssm"]`` as the whole stack and the
    layer (the kernel, in place) against the same call on the layer's rows
    (the XLA branch): the block's output on the live rows, both conv tails,
    the state; a reset row (position 0) starts from zeros on both."""
    rows, hidden = 4, 32
    spec = ssm.SSMSpec(kind="mamba2", d_inner=64, num_heads=4, head_dim=16,
                       d_state=128, n_groups=2, gated_norm=gated_norm)
    lw, state, x = _mixer_operands(spec, hidden, rows)
    valid = jnp.asarray([[True], [True], [False], [True]])
    positions = jnp.asarray([[5], [0], [9], [2]], jnp.int32)
    kw = dict(phase="paged", positions=positions, valid=valid)
    by_rows = {k: v[LAYER] for k, v in state.items()}
    want, st_want = jax.jit(lambda: ssm.mamba2_mixer(
        spec, lw, x, by_rows, **kw))()
    got, st_got = jax.jit(lambda: ssm.mamba2_mixer(
        spec, lw, x, {**by_rows, "ssm": ssm.StateStack(state["ssm"], LAYER)},
        **kw))()
    assert isinstance(st_got["ssm"], ssm.StateStack)
    assert st_got["ssm"].layer == LAYER
    live = np.asarray(valid[:, 0])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=1e-5)
    for k in ("conv_x", "conv_bc"):
        assert (np.asarray(st_got[k]) == np.asarray(st_want[k])).all()
    stack = np.asarray(st_got["ssm"].stack)
    np.testing.assert_allclose(stack[LAYER], np.asarray(st_want["ssm"]),
                               atol=2e-6, rtol=1e-5)
    # the dead row's slot and the other layers: bit for bit
    assert (stack[LAYER][2] == np.asarray(state["ssm"])[LAYER][2]).all()
    for other in set(range(LAYERS)) - {LAYER}:
        assert (stack[other] == np.asarray(state["ssm"])[other]).all()


@pytest.mark.parametrize("case, why", [
    (dict(tokens=8), "8 tokens a row: the chunked form"),
    (dict(state_slots=np.zeros((4,), np.int32)),
     "rows gathered from their slots"),
    (dict(rows=2), "rows gathered from their slots"),
    (dict(dtype=jnp.bfloat16), "state stored as bfloat16"),
    (dict(tile=(12, 128)), "4 tiles of 12x128 in 2 groups are not whole "
     "8x128 tiles under 4194304 bytes"),
    (dict(tile=(16, 64)), "4 tiles of 16x64 in 2 groups are not whole "
     "8x128 tiles under 4194304 bytes"),
    (dict(tile=(2048, 1024)), "4 tiles of 2048x1024 in 2 groups are not "
     "whole 8x128 tiles under 4194304 bytes"),
    (dict(), "")])
def test_what_the_rule_declines_is_named(case, why):
    hd, n = case.get("tile", (16, 128))
    spec = ssm.SSMSpec(kind="mamba2", d_inner=4 * hd, num_heads=4,
                       head_dim=hd, d_state=n, n_groups=2)
    stack = jax.ShapeDtypeStruct((3, 4, 4, hd, n),
                                 case.get("dtype", jnp.float32))
    assert ssm.state_kernel_declined(
        spec, stack, case.get("rows", 4), case.get("tokens", 1),
        case.get("state_slots")) == why
    if "tile" in case:
        with pytest.raises(ValueError, match="no state-step kernel"):
            jax.eval_shape(
                lambda s: mss.mamba_state_step(
                    s, 0, jnp.zeros((4, 4, hd)), jnp.zeros((4, 4)),
                    jnp.zeros((4, 2, n)), jnp.zeros((4, 2, n)),
                    jnp.ones((4,), bool), jnp.ones((4,), bool)), stack)


def test_granites_plan_is_the_rules_own():
    """64 heads of ``(64, 128)`` in one group go :data:`BLOCK_HEADS` = 32 a
    block, a mebibyte: the body is unrolled over a block's heads, and 32
    moved the state as fast as 64 at half the set-up. The delta rule caps a
    block at ``d_k`` heads (its ``beta`` rides in one row of ``d_k`` lanes);
    this rule has no such row and must not inherit the cap."""
    plan = mss.mamba_step_plan(64, 1, 64, 128)
    assert plan == dss.StateStepPlan(32, 64, 128)
    assert plan.note() == "heads=32 tile=64x128"
    assert mss.mamba_step_plan(128, 8, 8, 128).heads == 32        # > d_k
    # eight groups of 16 heads: blocks of whole groups, two here
    assert mss.mamba_step_plan(128, 8, 64, 128).heads == 32
    # the operands are reshapes: a block's B rows are its groups'
    assert mss._by_block(jnp.zeros((2, 1, 128)), 64, plan).shape == \
        (2, 2, 1, 128)
