"""``python -m pytest benchmark/tests -q`` — CPU, seconds each, no subprocess.

Puts the repo root and ``benchmark/`` on the path, and makes the CPU backend
ready the way the repo's own tests do (virtual devices, interpret-mode
kernels) before anything touches jax."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("USE_TF", "0")

from neuronx_distributed_inference_tpu.compat import force_cpu_devices  # noqa: E402

force_cpu_devices(4)
