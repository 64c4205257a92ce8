"""The walk over the touched experts (``ops/moe_decode.py``) against the
dense all-experts path and the grouped matmuls it replaces where it is
taken: the same combine-weighted sum over the experts the routing touched,
read out of the stack in place. A step of at most one tile of rows (a
decode step, a 64-token chunk) goes whole against every touched expert; a
one-row chunk of 256 hands the kernel its assignments sorted by expert.

Interpret mode (conftest asks for it). The geometries are OLMoE's (64
experts, top-8, every expert held) and qwen3-next's share (512 routed
over, 128 held from ``first_expert`` 128 on, top-10 renormalised) at toy
widths that keep their aspect, OLMoE's real widths with few experts (in
float32 an expert of 2048 x 1024 is walked in two pieces), and LongCat-Flash's
share beside identity columns (ISSUE 40: ``MoESpec.zero_experts``), and
SmallThinker's ReLU-gated experts (ISSUE 43: ``moe_decode.WALK_ACTS``)."""

import jax
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
    # decode steps are test_moe_decode_relu.py's: this file is the suite's
    # longest, and a file is one worker's
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


@pytest.mark.parametrize("rows, tokens", CHUNKS,
                         ids=[f"{r}x{t}" for r, t in CHUNKS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_a_chunk_equals_the_ragged_and_the_dense_path(name, dtype, rows,
                                                      tokens):
    """More rows than a tile: each touched expert against ITS rows. The
    walk == ``experts_ragged`` on the stack == ``experts_dense`` on the
    layer's slice within the dtype's rounding; ``read`` is the number of
    held experts with a row. 200 real tokens are padded to 256 with clones
    of the first, as a last chunk is; at OLMoE's widths an expert's group
    (~128 rows) is walked in more than one tile."""
    spec, x, router, stack = _case(name, dtype, rows, tokens, seed=tokens)
    if tokens == 200:
        x = jnp.concatenate([x, jnp.repeat(x[:, :1], 56, axis=1)], axis=1)
    layer = 1
    top_vals, top_idx = moe_mod.route(spec, x, router)
    dense = moe_mod.experts_dense(spec, x, top_vals, top_idx,
                                  *(w[layer] for w in stack))
    ragged = moe_mod.experts_ragged(spec, x, top_vals, top_idx, *stack,
                                    layer=layer)
    assert x.shape[0] * x.shape[1] > moe_decode.ROW_TILE
    got, read = moe_mod.experts_touched(spec, x, top_vals, top_idx, *stack,
                                        layer)
    assert got.dtype == x.dtype and got.shape == x.shape
    for want in (dense, ragged):
        np.testing.assert_allclose(_f32(got), _f32(want), rtol=0,
                                   atol=_tolerance(dtype, dense))
    if tokens == 200:
        np.testing.assert_array_equal(
            _f32(got)[0, 200:], np.broadcast_to(_f32(got)[0, :1],
                                                (56, x.shape[2])))
    combine = np.asarray(moe_mod.held_combine(spec, top_vals, top_idx))
    assert int(read) == np.count_nonzero(
        combine.reshape(-1, spec.num_held).any(axis=0))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_chunk_with_groups_of_none_one_and_many_rows(dtype):
    """Side by side in one chunk of 256: an expert with no row (never
    read: NaN weights), one with ONE row, one with all 256 (two tiles),
    one with 129 (a tile and one row) - still the dense path's sum."""
    spec = moe_mod.MoESpec(num_experts=8, top_k=2, intermediate_size=128)
    rng = np.random.default_rng(39)

    def leaf(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.05, dtype)
    stack = [leaf(2, 8, 256, 128), leaf(2, 8, 256, 128), leaf(2, 8, 128, 256)]
    x = jnp.asarray(rng.normal(size=(1, 256, 256)), dtype)
    # every token to expert 2; token 7 also to 5; the first 129 of the
    # rest also to 6, the others to 0; experts 1, 3, 4, 7 get nothing
    second = np.where(np.arange(256) < 130, 6, 0)
    second[7] = 5
    top_idx = jnp.asarray(np.stack([np.full(256, 2), second], -1)[None],
                          jnp.int32)
    top_vals = jnp.asarray(rng.uniform(0.1, 0.9, size=(1, 256, 2)),
                           jnp.float32)
    want = moe_mod.experts_dense(spec, x, top_vals, top_idx,
                                 *(w[1] for w in stack))
    untouched = np.zeros((2, 8, 1, 1), bool)
    untouched[:, [1, 3, 4, 7]] = True
    untouched[0] = True
    poisoned = [jnp.where(untouched, jnp.nan, w) for w in stack]
    got, read = moe_mod.experts_touched(spec, x, top_vals, top_idx,
                                        *poisoned, 1)
    assert int(read) == 4
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=0,
                               atol=_tolerance(dtype, want))


@pytest.mark.parametrize("n, h, e, i, dtype, fits", [
    (32, 2048, 64, 1024, jnp.bfloat16, True),       # OLMoE's decode step
    (64, 2048, 128, 512, jnp.bfloat16, True),       # the 64-token chunk
    (256, 2048, 128, 512, jnp.bfloat16, True),      # qwen3-next's chunk
    (256, 2048, 64, 1024, jnp.bfloat16, True),      # OLMoE's chunk
    (256, 6144, 16, 2048, jnp.bfloat16, True),      # longcat's chunk
    (1024, 2048, 64, 1024, jnp.bfloat16, False),    # OLMoE's 64 pack
    (2048, 2048, 128, 512, jnp.bfloat16, False),    # qwen3-next's 64 pack
    (8192, 2048, 128, 512, jnp.bfloat16, False),
], ids=["decode", "w64", "qwen3-next-w256", "olmoe-w256", "longcat-w256",
        "olmoe-pack-w64", "pack-w64", "pack-w256"])
def test_the_rows_vmem_follows_the_rows(n, h, e, i, dtype, fits):
    """What the call asks of VMEM beside the slots is computed from the
    rows it carries, grows with them, and a step whose rows would need
    more than ``MOE_ROWS_VMEM_BYTES``, or that is longer than
    ``MOE_WALK_MAX_ROWS``, is declined by name."""
    plan = moe_decode.moe_decode_plan(h, i, dtype)
    need = moe_decode.rows_vmem_bytes(n, h, e, plan, dtype)
    assert need > moe_decode.rows_vmem_bytes(n // 2, h, e, plan, dtype)
    assert (need <= moe_decode.MOE_ROWS_VMEM_BYTES
            and n <= moe_decode.MOE_WALK_MAX_ROWS) == fits
    spec = moe_mod.MoESpec(num_experts=e, top_k=8, intermediate_size=i)
    why = moe_decode.declined(spec, jax.ShapeDtypeStruct((2, e, h, i), dtype),
                              n)
    assert (why == "") == fits
    assert fits or why == f"{n} rows of {h} do not fit VMEM beside the slots"


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


@pytest.mark.parametrize("h, i, dtype, want", [
    (2048, 1024, jnp.bfloat16, (1, 1024)),      # OLMoE: 3 x 4 MiB a slot
    (2048, 512, jnp.bfloat16, (1, 512)),        # qwen3-next: 3 x 2 MiB
    (2048, 1024, jnp.float32, (2, 512)),
    (4096, 14336, jnp.bfloat16, (28, 512)),     # mixtral: 112 MiB a matrix
    (64, 128, jnp.float32, None),               # the toys: no whole tiles
    (2048, 1000, jnp.bfloat16, None),
], ids=["olmoe", "qwen3-next", "olmoe-f32", "mixtral", "toy", "ragged-i"])
def test_the_plan_follows_bytes(h, i, dtype, want):
    plan = moe_decode.moe_decode_plan(h, i, dtype)
    assert (plan and tuple(plan)) == want
    if plan:
        assert (2 * 3 * h * plan.ip * jnp.dtype(dtype).itemsize
                <= moe_decode.MOE_WEIGHT_VMEM_BYTES)


def test_the_touched_list_is_ascending_and_compact():
    combine = np.zeros((4, 16), np.float32)
    combine[0, [3, 9]] = 0.5
    combine[2, [9, 15, 0]] = 0.25
    ids, count = moe_decode.touched_experts(jnp.asarray(combine))
    assert int(count) == 4
    assert np.asarray(ids)[:4].tolist() == [0, 3, 9, 15]
    assert np.asarray(ids).max() < 16


@pytest.mark.parametrize("name, dtype, rows, tokens", [
    ("olmoe", jnp.float32, 2, 1),
    ("qwen3-next-share", jnp.bfloat16, 1, 256),
], ids=["decode", "chunk"])
def test_kernel_under_jit_with_a_traced_layer(name, dtype, rows, tokens):
    """The layer a traced scalar, as a scan hands it in: a decode step,
    and the chunk's form of the kernel (as the one-row chunk program's
    layer scan runs it)."""
    spec, x, router, stack = _case(name, dtype, rows, tokens, seed=3)
    top_vals, top_idx = moe_mod.route(spec, x, router)

    @jax.jit
    def walk(x):
        def body(carry, li):
            y, read = moe_mod.experts_touched(spec, x, top_vals, top_idx,
                                              *stack, li)
            return carry, (y, read)
        return jax.lax.scan(body, 0, jnp.arange(LAYERS, dtype=jnp.int32))[1]
    ys, reads = walk(x)
    for li in range(LAYERS):
        want = moe_mod.experts_dense(spec, x, top_vals, top_idx,
                                     *(w[li] for w in stack))
        np.testing.assert_allclose(_f32(ys[li]), _f32(want), rtol=0,
                                   atol=_tolerance(dtype, want))
    assert len(set(np.asarray(reads).tolist())) == 1
