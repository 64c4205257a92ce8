"""What the program runs on: the accelerator check every measurement path
makes before it measures, and the one table of chip peaks.

A measurement path that finds no chip fails (:func:`require_tpu`); it never
falls back to the CPU. A roofline is taken against the peaks of a NAMED
``device_kind`` (:func:`device_peaks`); a kind that is not in the table is
an error, not a default.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax


class NoAcceleratorError(RuntimeError):
    """The first jax device is not a TPU."""


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks. ``dcn_gbps`` is NOT a chip property: it is
    the per-chip share of the host NIC the scale-out projection assumes."""

    bf16_tflops: float
    hbm_gbps: float
    hbm_gib: float
    ici_gbps: float
    dcn_gbps: float
    source: str


#: keyed by ``jax.Device.device_kind``
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_tflops=197.0, hbm_gbps=819.0, hbm_gib=16.0,
        ici_gbps=200.0,          # 1,600 Gbit/s chip-to-chip
        dcn_gbps=25.0,           # assumed: 200 Gbit/s host NIC
        source="Google Cloud documentation, 'TPU v5e' system architecture"),
}

#: the chip the static (CPU-run) projections are written against
V5E = "TPU v5 lite"


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} — add the chip's published numbers to "
            "utils/device.py DEVICE_PEAKS with their source") from None


def require_tpu() -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` of the devices jax reports, or
    :class:`NoAcceleratorError` when the first one is not a TPU."""
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoAcceleratorError(
            f"no TPU: jax.devices()[0] is platform={d0.platform!r} "
            f"kind={d0.device_kind!r} — this path measures the chip and "
            "does not fall back to another backend")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def versions() -> Dict[str, str]:
    """jax / jaxlib / libtpu versions, as installed."""
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "not installed"
    return out
