"""Test env: force a virtual 8-device CPU mesh so sharding/collective logic is
exercised without TPU hardware (reference analog: NXD_CPU_MODE + gloo fake
distributed backend, utils/testing.py:40-64). Pallas kernels run in
interpret mode because this file asks for it (ops/kernel_mode.py)."""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent XLA compile cache shared across the whole suite (and inherited
# by subprocess tests through the env var): the tier-1 wall clock is
# dominated by recompiling the same tiny graphs in every module, and the
# 870s budget is tight on slow host phases. A fixed per-user directory
# OUTSIDE the checkout, so the suite never fills the tree the chip tool
# has to copy.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(),
                 "nxdi_tpu_test_xla_cache_%s" % os.environ.get("USER",
                                                               "root")))

import jax  # noqa: E402

from neuronx_distributed_inference_tpu.compat import \
    force_cpu_devices  # noqa: E402
from neuronx_distributed_inference_tpu.utils.compile_cache import \
    configure_compile_cache  # noqa: E402

force_cpu_devices(8)
configure_compile_cache()
jax.config.update("jax_threefry_partitionable", True)
# fp32 tests compare against torch exactly; don't let matmuls drop precision
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def tiny_llama_hf_config(**over):
    """4-layer random-weight tiny config (reference test strategy:
    test/integration tiny models with num_hidden_layers=4, SURVEY §4)."""
    cfg = dict(
        model_type="llama",
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        vocab_size=512,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        max_position_embeddings=256,
        hidden_act="silu",
        tie_word_embeddings=False,
        torch_dtype="float32",
    )
    cfg.update(over)
    return cfg


@pytest.fixture
def tiny_config_dict():
    return tiny_llama_hf_config()


def load_nxdi_lint():
    """Import scripts/nxdi_lint.py (and through it the stdlib-only
    analysis package) once, shared by every lint-asserting test module —
    no subprocess, no second copy of the registry."""
    import importlib.util
    import sys as _sys
    if "nxdi_lint" in _sys.modules:
        return _sys.modules["nxdi_lint"]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "nxdi_lint", os.path.join(repo, "scripts", "nxdi_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    _sys.modules["nxdi_lint"] = mod
    spec.loader.exec_module(mod)
    return mod
