"""Fleet layer — the tier above one ServingEngine (ROADMAP item 3).

Three composable prongs, all CPU-verifiable (README "Fleet" is the
contract):

  * :class:`~.router.EngineRouter` — spreads requests across N
    :class:`~..engine.scheduler.ServingEngine` replicas with
    prefix-affinity routing (warmest ``prefix_warmth``, tie-broken by
    least queue depth from ``debug_state()``), a per-replica health
    state machine (healthy/draining/backing_off/probation/dead —
    retry-safe step failures quarantine a replica behind exponential
    backoff with seeded jitter, a clean probing pass re-admits it
    without operator ``undrain()``), and requeue-on-replica-failure
    riding the ``Preempted`` requeue contract (failover streams stay
    bit-identical under greedy decoding);
  * :class:`~.kv_tier.HostKVSpillTier` — a bounded host-RAM tier under
    the device block pool: LRU-evicted prefix blocks spill their
    payloads host-side (content-hash keyed) and re-admit via async H2D
    restore instead of recompute-prefill;
  * :mod:`~.handoff` — disaggregated prefill: a prefill-role engine
    captures a JSON-safe handoff record (serialized ``Preempted`` + the
    spilled KV block payloads) that a decode-role engine admits through
    the ordinary transactional ``add_requests`` path, bit-identical to a
    single-engine run.

Elastic on top (ISSUE 17): :func:`~.handoff.migrate` moves a
MID-DECODE stream between replicas with its KV (the handoff wire form,
live), :class:`~.autoscaler.FleetAutoscaler` closes the loop on fleet
signals (queue / SLO burn / admission headroom) to resize the replica
set with precompile-first admission and drain-by-migration retirement.
"""

from .aggregator import FleetMetricsAggregator
from .autoscaler import FleetAutoscaler
from .handoff import (HANDOFF_SCHEMA, admit_handoff, capture_handoff,
                      handoff_from_json, handoff_to_json, migrate)
from .kv_tier import HostKVSpillTier
from .router import (BACKING_OFF, DEAD, DRAINING, HEALTHY, PROBATION,
                     EngineRouter)

__all__ = [
    "EngineRouter", "HEALTHY", "DRAINING", "BACKING_OFF", "PROBATION",
    "DEAD",
    "HostKVSpillTier", "FleetMetricsAggregator", "FleetAutoscaler",
    "HANDOFF_SCHEMA", "capture_handoff", "admit_handoff", "migrate",
    "handoff_to_json", "handoff_from_json",
]
