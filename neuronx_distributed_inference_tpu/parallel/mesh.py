"""Device mesh construction — the TPU-native replacement for the reference's
process-group zoo (reference: neuronx_distributed ``parallel_state``
``initialize_model_parallel`` and
src/neuronx_distributed_inference/modules/attention/attention_process_groups.py).

Instead of materializing TP/CP/DP/EP process groups, we build ONE
``jax.sharding.Mesh`` with named axes and express each parallelism strategy as
a PartitionSpec over those axes:

  axis "dp" — attention data parallel (decode batch sharding,
              reference: attention_process_groups.py:125-163)
  axis "cp" — context parallel (prefill sequence sharding,
              reference: attention_process_groups.py:81-123)
  axis "tp" — tensor parallel (heads / hidden sharding)
  axis "ep" — expert parallel (MoE expert sharding, reference: modules/moe_v2.py:135-161)

The reference's phase asymmetry (CP groups for prefill, DP groups for decode
over the SAME ranks — attention_base.py:183-199) maps here to *reusing* the
``cp`` axis: during prefill activations shard sequence over ("dp","cp"), during
decode the batch shards over ("dp","cp"). The mesh itself never changes, only
the PartitionSpecs, so no KV-head reshuffling between phases is required when
layouts are chosen consistently.

Multi-host: ``jax.distributed.initialize`` over DCN replaces the reference's
MPI + NEURON_RT_ROOT_COMM_ID bootstrap
(reference: scripts/nxdi_distributed_launcher.py:29-85).
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger("nxdi_tpu")

# Canonical axis order: outermost (slowest-varying, DCN-friendly) first.
# ep sits OUTSIDE tp: the reference's moe_tp_degree x moe_ep_degree factors the
# tp rank set (modules/moe_v2.py:135-161); here "model parallel" dims (heads,
# mlp intermediate, vocab) shard over the COMBINED ("ep","tp") axes while MoE
# expert weights shard experts over "ep" and intermediate over "tp".
AXIS_DP = "dp"
AXIS_CP = "cp"
AXIS_TP = "tp"
AXIS_EP = "ep"
MESH_AXES = (AXIS_DP, AXIS_CP, AXIS_EP, AXIS_TP)

# Composite model-parallel spec entry: full tp_degree sharding of a dim.
AXIS_MP = (AXIS_EP, AXIS_TP)


@dataclass(frozen=True)
class MeshConfig:
    tp: int = 1
    cp: int = 1
    dp: int = 1
    ep: int = 1

    @property
    def world_size(self) -> int:
        # cp/dp/ep all subdivide the model-parallel rank set during different
        # phases/blocks; the physical world is dp*cp*ep*tp
        # (moe_tp x moe_ep = tp, reference: modules/moe_v2.py:135-161).
        return self.dp * self.cp * self.ep * self.tp


@dataclass(frozen=True)
class Topology:
    """Physical placement of the mesh axes: which axes cross the DCN boundary.

    A single slice rides ICI end to end. Scaling out — the 70B-on-v5e-32
    shape is dp4(x)tp8 over four 8-chip hosts — puts the OUTERMOST mesh axes
    on the data-center network, which is ~an order of magnitude slower than
    ICI (priced by the observatory at ``NXDI_TPU_DCN_GBPS`` vs
    ``NXDI_TPU_ICI_GBPS``). MESH_AXES is ordered outermost-first exactly so
    the dp axis is the one that can leave the slice: dp traffic is
    whole-replica independent during decode (no per-step all-reduce), so it
    tolerates DCN latency where tp cannot.
    """

    dcn_axes: Tuple[str, ...] = ()

    def is_dcn(self, comm_axes) -> bool:
        """True when a collective over ``comm_axes`` crosses the DCN."""
        return any(a in self.dcn_axes for a in comm_axes)


#: single-slice default — every axis on ICI
SINGLE_SLICE = Topology()
#: the scale-out shape: dp crosses the DCN boundary, tp/ep/cp stay on ICI
DP_OVER_DCN = Topology(dcn_axes=(AXIS_DP,))


def topology_from_env() -> Topology:
    """Resolve the deployment topology from ``NXDI_TPU_DCN_AXES`` (comma
    separated mesh axis names; default "dp" — the conservative pricing:
    anything dp-attributed is assumed to cross the DCN)."""
    raw = os.environ.get("NXDI_TPU_DCN_AXES", AXIS_DP)
    axes = tuple(a for a in (s.strip() for s in raw.split(",")) if a)
    bad = [a for a in axes if a not in MESH_AXES]
    if bad:
        raise ValueError(f"NXDI_TPU_DCN_AXES names unknown mesh axes {bad}; "
                         f"expected a subset of {MESH_AXES}")
    return Topology(dcn_axes=axes)


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap over DCN. Safe no-op for single-process runs.

    Replaces the reference's MPI launcher + gloo host barrier
    (reference: inference_demo.py:788-796, scripts/nxdi_distributed_launcher.py).
    """
    if num_processes is None:
        num_processes = int(os.environ.get("NXDI_TPU_NUM_PROCESSES", "1"))
    if num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def build_mesh(cfg: MeshConfig, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the (dp, cp, ep, tp) mesh.

    Device order follows jax.devices() which is ICI-contiguous — tp innermost
    so tp collectives ride the fastest links; ep just outside so MoE expert
    dispatch stays intra-slice.
    """
    if devices is None:
        devices = jax.devices()
    n = cfg.dp * cfg.cp * cfg.ep * cfg.tp
    if len(devices) < n:
        raise ValueError(f"mesh needs {n} devices (dp={cfg.dp} cp={cfg.cp} "
                         f"ep={cfg.ep} tp={cfg.tp}), only {len(devices)} available")
    dev_array = np.array(devices[:n]).reshape(cfg.dp, cfg.cp, cfg.ep, cfg.tp)
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh() -> Mesh:
    return build_mesh(MeshConfig())


def mesh_from_config(tpu_config,
                     devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build mesh from a TpuConfig's parallelism degrees, over ``devices``
    (default: the first ``tp_degree`` of ``jax.devices()``). Several
    replicas in one process each name their own devices — left to the
    default they would all land on device 0."""
    # attention-DP / CP / EP all subdivide the tp rank set in the reference
    # (tp_degree counts ALL ranks; cp/dp/ep are groupings of them:
    # attention_process_groups.py:36-163, moe_v2.py:135-161). Here tp axis =
    # tp/(cp*dp*ep), so the physical world stays tp_degree devices.
    cp = max(tpu_config.cp_degree, 1)
    dp = max(tpu_config.attention_dp_degree, 1)
    ep = max(tpu_config.ep_degree, 1)
    shrink = cp * dp * ep
    if tpu_config.tp_degree % shrink != 0:
        raise ValueError(f"tp_degree {tpu_config.tp_degree} not divisible by "
                         f"cp*dp*ep = {shrink}")
    return build_mesh(MeshConfig(tp=tpu_config.tp_degree // shrink, cp=cp, dp=dp,
                                 ep=ep), devices)


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

def shard_constraint(x, *spec):
    """``with_sharding_constraint`` that no-ops outside a mesh context —
    the shared helper for model code (traced under jit with a mesh active;
    plain-eager tests run without one). Inside a mesh a constraint that
    cannot be applied raises: an unsharded activation is not a fallback."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch_spec(mesh: Mesh) -> P:
    """Decode-phase batch sharding over dp (and cp when cp>1 is repurposed,
    reference: DataParallelKVCacheManager)."""
    axes = [a for a, s in zip(mesh.axis_names, mesh.devices.shape) if s > 1
            and a in (AXIS_DP, AXIS_CP)]
    return P(tuple(axes) if axes else None)


def logical_to_physical(rules: dict, logical_axes: Tuple[Optional[str], ...]) -> P:
    """Map logical axis names (e.g. ("batch", "seq", "hidden")) to mesh axes."""
    return P(*[rules.get(a) if a is not None else None for a in logical_axes])


# Default logical->mesh rules for decoder LLMs.
DEFAULT_RULES = {
    "batch": AXIS_DP,
    "seq": None,            # sequence sharded only under SP/CP via explicit specs
    "hidden": None,
    "heads": AXIS_MP,
    "kv_heads": AXIS_MP,
    "mlp": AXIS_MP,
    "vocab": AXIS_MP,
    "expert": AXIS_EP,
    "expert_mlp": AXIS_TP,  # intermediate dim inside an expert (moe_tp)
    "layer": None,
}
