"""The CPU stand-in for the accelerator: N virtual devices and
interpret-mode Pallas kernels, for runs that check sharding and control
flow without a chip (``__graft_entry__.py``, the lint scripts,
``inference_demo --on-cpu``)."""

from __future__ import annotations

import jax

from .ops.kernel_mode import request_interpret

__all__ = ["force_cpu_devices"]


def force_cpu_devices(n: int = 8) -> None:
    """Point jax at ``n`` virtual CPU devices and ask for interpret-mode
    kernels. Call before the backend initializes: once it has, the
    platform/device-count updates raise and the live backend stays."""
    request_interpret()
    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backend already initialized; nothing more we can do
