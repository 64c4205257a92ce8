"""The walk over the touched experts (``ops/moe_decode.py``) against the
dense all-experts path, at a step of at most one tile of rows (a decode
step, a 64-token chunk): the same combine-weighted sum over the experts the
routing touched, read out of the stack in place, the whole step against
every touched expert. The chunk's form (more rows than a tile) is
``test_moe_decode_chunk.py``'s, the plan, the VMEM arithmetic and the traced
layer ``test_moe_decode_plan.py``'s, SmallThinker's decode steps
``test_moe_decode_relu.py``'s: a file is one worker's under the tier-1
command (``--dist loadfile``), and together they were the run's length. The
geometries and ``_case`` live here; the other three import them.

Interpret mode (conftest asks for it). The geometries are OLMoE's (64
experts, top-8, every expert held) and qwen3-next's share (512 routed
over, 128 held from ``first_expert`` 128 on, top-10 renormalised) at toy
widths that keep their aspect, OLMoE's real widths with few experts (in
float32 an expert of 2048 x 1024 is walked in two pieces), and LongCat-Flash's
share beside identity columns (ISSUE 40: ``MoESpec.zero_experts``), and
SmallThinker's ReLU-gated experts (ISSUE 43: ``moe_decode.WALK_ACTS``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.modules import moe as moe_mod
from neuronx_distributed_inference_tpu.ops import moe_decode

LAYERS = 3

GEOMETRIES = {
    # name: (spec, hidden, experts held)
    "olmoe": (moe_mod.MoESpec(num_experts=64, top_k=8, intermediate_size=128,
                              normalize_topk=False), 256, 64),
    "qwen3-next-share": (moe_mod.MoESpec(
        num_experts=512, top_k=10, intermediate_size=128, held_experts=128,
        first_expert=128), 512, 128),
    "olmoe-widths": (moe_mod.MoESpec(num_experts=4, top_k=2,
                                     intermediate_size=1024), 2048, 4),
    # LongCat-Flash's share: 32 held of 64 routed ones beside 32 identity
    # columns, top-12 x 6 and not renormalised; a pick of an identity expert
    # is no expert of the stack's
    "longcat-share": (moe_mod.MoESpec(
        num_experts=64 + 32, top_k=12, intermediate_size=128,
        held_experts=32, first_expert=16, zero_experts=32,
        normalize_topk=False, routed_scaling=6.0), 256, 32),
    # SmallThinker's experts (ISSUE 43): ReLU-gated, the top-6 of the
    # logits and the softmax over those; the zeros are not exploited. Its
    # decode steps are test_moe_decode_relu.py's
    "smallthinker-relu": (moe_mod.MoESpec(
        num_experts=64, top_k=6, intermediate_size=128,
        pre_softmax_topk=True, act="relu"), 256, 64),
}

# (rows, tokens a row): decode steps of 1, 2, 16 and 32 rows and the
# one-row 64-token chunk
STEPS = [(1, 1), (2, 1), (16, 1), (32, 1), (1, 64)]
# the one-row chunk of 256 tokens, 200 real tokens + 56 pad clones of the
# first (a padded last chunk), and an uneven count that is no whole tile
CHUNKS = [(1, 256), (1, 200), (3, 67)]


def _case(name, dtype, rows, tokens, seed=0, layers=LAYERS):
    spec, hidden, held = GEOMETRIES[name]
    rng = np.random.default_rng(seed)

    def leaf(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.05, dtype)
    inter = spec.intermediate_size
    stack = (leaf(layers, held, hidden, inter), leaf(layers, held, hidden,
                                                     inter),
             leaf(layers, held, inter, hidden))
    x = jnp.asarray(rng.normal(size=(rows, tokens, hidden)), dtype)
    router = jnp.asarray(rng.normal(size=(hidden, spec.num_experts)),
                         jnp.float32)
    return spec, x, router, stack


def _tolerance(dtype, want):
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    return (2e-2 if dtype == jnp.bfloat16 else 1e-5) * scale


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("layer", [0, LAYERS // 2, LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("rows, tokens", STEPS,
                         ids=[f"{r}x{t}" for r, t in STEPS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["olmoe", "qwen3-next-share",
                                  "longcat-share"])
def test_kernel_equals_the_dense_path(name, dtype, rows, tokens, layer):
    """kernel == ``experts_dense`` on the layer's slice within the dtype's
    rounding; the tally's ``read`` is the touched list's length and is at
    least ``touched``."""
    spec, x, router, stack = _case(name, dtype, rows, tokens,
                                   seed=rows + layer)
    top_vals, top_idx = moe_mod.route(spec, x, router)
    want = moe_mod.experts_dense(spec, x, top_vals, top_idx,
                                 *(w[layer] for w in stack))
    got, read = moe_mod.experts_touched(spec, x, top_vals, top_idx, *stack,
                                        layer)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=0,
                               atol=_tolerance(dtype, want))
    combine = moe_mod.held_combine(spec, top_vals, top_idx)
    listed = int(np.count_nonzero(np.asarray(combine).reshape(
        -1, spec.num_held).any(axis=0)))
    live = jnp.arange(rows)[:, None] < max(1, rows // 2)
    touched, assigned, tallied = np.asarray(moe_mod.share_tally(
        spec, top_idx, jnp.broadcast_to(live, x.shape[:2]), read))
    assert int(read) == tallied == listed
    assert touched <= tallied <= spec.num_held and touched <= assigned


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_untouched_experts_are_not_read(name, dtype):
    """NaN written into every UNTOUCHED expert's weights (and into every
    other layer) leaves the result bit-equal: they were not read."""
    spec, x, router, stack = _case(name, dtype, 2, 1, seed=7)
    top_vals, top_idx = moe_mod.route(spec, x, router)
    layer = 1
    combine = np.asarray(moe_mod.held_combine(spec, top_vals, top_idx))
    hit = combine.reshape(-1, spec.num_held).any(axis=0)
    assert 0 < hit.sum() < spec.num_held
    keep = np.zeros((LAYERS, spec.num_held, 1, 1), bool)
    keep[layer, hit] = True
    poisoned = tuple(jnp.where(keep, w, jnp.nan) for w in stack)
    want, _ = moe_mod.experts_touched(spec, x, top_vals, top_idx, *stack,
                                      layer)
    got, read = moe_mod.experts_touched(spec, x, top_vals, top_idx, *poisoned,
                                        layer)
    assert int(read) == hit.sum()
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_array_equal(_f32(got), _f32(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["olmoe", "qwen3-next-share"])
def test_a_clone_of_row_0_comes_out_as_row_0(name, dtype):
    """Pad rows are clones of row 0: whatever else is in the step, they
    come out bit-equal to it, and they add nothing to what is read."""
    spec, x, router, stack = _case(name, dtype, 3, 1, seed=11)
    x = jnp.concatenate([x, jnp.repeat(x[:1], 13, axis=0)])       # 16 rows
    top_vals, top_idx = moe_mod.route(spec, x, router)
    got, read = moe_mod.experts_touched(spec, x, top_vals, top_idx, *stack,
                                        2)
    got = _f32(got)
    for row in range(3, 16):
        np.testing.assert_array_equal(got[row], got[0])
    _, read3 = moe_mod.experts_touched(spec, x[:3], top_vals[:3],
                                       top_idx[:3], *stack, 2)
    assert int(read) == int(read3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["olmoe", "olmoe-widths"])
def test_all_experts_touched(name, dtype):
    """Every held expert on the list (the long-prompt cell's case): still
    the dense path's sum, ``read`` the whole layer; at OLMoE's widths in
    float32 each expert is walked in two column pieces."""
    spec, hidden, held = GEOMETRIES[name]
    spec, x, router, stack = _case(name, dtype, 16, 1, seed=5, layers=2)
    # row r routes to experts r*k .. r*k + k - 1 (mod held): all of them
    top_idx = (jnp.arange(16 * spec.top_k, dtype=jnp.int32) % held).reshape(
        16, 1, spec.top_k)
    top_vals = jnp.full(top_idx.shape, 1.0 / spec.top_k, jnp.float32)
    want = moe_mod.experts_dense(spec, x, top_vals, top_idx,
                                 *(w[1] for w in stack))
    got, read = moe_mod.experts_touched(spec, x, top_vals, top_idx, *stack, 1)
    assert int(read) == held
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=0,
                               atol=_tolerance(dtype, want))
    plan = moe_decode.moe_decode_plan(hidden, spec.intermediate_size, dtype)
    if name == "olmoe-widths":
        assert plan.pieces == (2 if dtype == jnp.float32 else 1)


@pytest.mark.parametrize("rows, tokens", [(2, 1), (1, 256)],
                         ids=["decode", "chunk"])
def test_no_held_expert_touched(rows, tokens):
    """A share none of whose experts the routing chose reads nothing and
    returns zeros: a decode step, and a chunk of 256."""
    spec, x, router, stack = _case("qwen3-next-share", jnp.float32, rows,
                                   tokens)
    top_idx = jnp.zeros((rows, tokens, spec.top_k), jnp.int32) + jnp.arange(
        spec.top_k, dtype=jnp.int32)               # experts 0..9: not held
    top_vals = jnp.full(top_idx.shape, 0.1, jnp.float32)
    got, read = moe_mod.experts_touched(spec, x, top_vals, top_idx, *stack, 0)
    assert int(read) == 0 and not _f32(got).any()
