"""Profiling — jax.profiler integration (reference: utils/profiling.py,
which shells out to ``neuron-profile capture`` on compiled NEFFs; the TPU
equivalent is the XLA/TPU profiler whose traces open in TensorBoard /
Perfetto, SURVEY §5)."""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Any, Dict, Optional

logger = logging.getLogger("nxdi_tpu")


@contextlib.contextmanager
def profile(log_dir: str = "profiles", host_tracer_level: int = 2):
    """Trace everything in the with-block; view with
    ``tensorboard --logdir <log_dir>`` (profile plugin) or xprof."""
    import jax
    os.makedirs(log_dir, exist_ok=True)
    logger.info("profiler: tracing to %s", log_dir)
    with jax.profiler.trace(log_dir):
        yield log_dir


def profile_generate(app, input_ids, log_dir: str = "profiles",
                     **generate_kwargs) -> Dict[str, Any]:
    """Profile one generate() call end-to-end (reference:
    utils/profiling.py capture flow: warm first, then trace)."""
    import jax
    # warm compile outside the trace so the profile shows steady-state
    app.generate(input_ids, **{**generate_kwargs,
                               "max_new_tokens": min(
                                   2, generate_kwargs.get("max_new_tokens", 2))})
    app.reset()
    t0 = time.perf_counter()
    with profile(log_dir):
        out = app.generate(input_ids, **generate_kwargs)
        jax.block_until_ready(out.get("generated"))
    out["profile_dir"] = log_dir
    out["profiled_wall_s"] = time.perf_counter() - t0
    return out
