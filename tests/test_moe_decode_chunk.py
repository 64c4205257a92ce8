"""The walk over the touched experts at MORE rows than a tile
(``moe_chunk_experts``: a one-row chunk of 256 hands the kernel its
assignments sorted by expert) against the grouped matmuls it replaces and
the dense all-experts path. ``test_moe_decode.py`` has the geometries and
the decode steps; these cases were that file's, and are in one of their own
because the tier-1 command gives a file to one worker (``--dist
loadfile``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.modules import moe as moe_mod
from neuronx_distributed_inference_tpu.ops import moe_decode

import test_moe_decode as base


@pytest.mark.parametrize("rows, tokens", base.CHUNKS,
                         ids=[f"{r}x{t}" for r, t in base.CHUNKS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(base.GEOMETRIES))
def test_a_chunk_equals_the_ragged_and_the_dense_path(name, dtype, rows,
                                                      tokens):
    """More rows than a tile: each touched expert against ITS rows. The
    walk == ``experts_ragged`` on the stack == ``experts_dense`` on the
    layer's slice within the dtype's rounding; ``read`` is the number of
    held experts with a row. 200 real tokens are padded to 256 with clones
    of the first, as a last chunk is; at OLMoE's widths an expert's group
    (~128 rows) is walked in more than one tile."""
    spec, x, router, stack = base._case(name, dtype, rows, tokens, seed=tokens)
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
        np.testing.assert_allclose(base._f32(got), base._f32(want), rtol=0,
                                   atol=base._tolerance(dtype, dense))
    if tokens == 200:
        np.testing.assert_array_equal(
            base._f32(got)[0, 200:], np.broadcast_to(base._f32(got)[0, :1],
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
    np.testing.assert_allclose(base._f32(got), base._f32(want), rtol=0,
                               atol=base._tolerance(dtype, want))
