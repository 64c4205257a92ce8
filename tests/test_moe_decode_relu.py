"""SmallThinker's ReLU-gated experts on the few-token expert kernel (ISSUE 43:
``moe_decode.WALK_ACTS``): ``test_moe_decode.py``'s decode steps and 64-token
chunk on its geometry ``smallthinker-relu``. In a file of their own because
the tier-1 command gives a file to one worker (``--dist loadfile``). The
geometry's wider chunks are cases of ``test_moe_decode_chunk.py``, its
untouched experts of ``test_moe_decode.py``."""

import jax.numpy as jnp
import pytest

import test_moe_decode as base


@pytest.mark.parametrize("layer", [0, base.LAYERS // 2, base.LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("rows, tokens", base.STEPS,
                         ids=[f"{r}x{t}" for r, t in base.STEPS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_relu_kernel_equals_the_dense_path(dtype, rows, tokens, layer):
    base.test_kernel_equals_the_dense_path("smallthinker-relu", dtype, rows,
                                           tokens, layer)
