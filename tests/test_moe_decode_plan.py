"""The few-token expert kernel's plan and what it asks of VMEM, computed
from shapes (no kernel runs), the touched list, and the kernel under ``jit``
with the layer a traced scalar, as a scan hands it in. ``test_moe_decode.py``
has the geometries; these cases were that file's, and are in one of their
own because the tier-1 command gives a file to one worker (``--dist
loadfile``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.modules import moe as moe_mod
from neuronx_distributed_inference_tpu.ops import moe_decode

import test_moe_decode as base


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
    spec, x, router, stack = base._case(name, dtype, rows, tokens, seed=3)
    top_vals, top_idx = moe_mod.route(spec, x, router)

    @jax.jit
    def walk(x):
        def body(carry, li):
            y, read = moe_mod.experts_touched(spec, x, top_vals, top_idx,
                                              *stack, li)
            return carry, (y, read)
        return jax.lax.scan(body, 0, jnp.arange(base.LAYERS,
                                                dtype=jnp.int32))[1]
    ys, reads = walk(x)
    for li in range(base.LAYERS):
        want = moe_mod.experts_dense(spec, x, top_vals, top_idx,
                                     *(w[li] for w in stack))
        np.testing.assert_allclose(base._f32(ys[li]), base._f32(want), rtol=0,
                                   atol=base._tolerance(dtype, want))
    assert len(set(np.asarray(reads).tolist())) == 1
