"""Serving resilience layer: typed failure hierarchy, deterministic
fault injection, and recompute-preemption policies.

The serving adapters (``serving.py``) and the paged cache manager
(``modules/block_kv_cache.py``) raise ONLY exceptions from this hierarchy at
their public boundaries (enforced by the ``error-paths`` pass of
``scripts/nxdi_lint.py``, a
tier-1 lint). Every recovery path — transactional admission rollback,
preemption under KV pressure, deadline budgets — is exercised on CPU by
arming the fault points in :mod:`.faults`; no TPU, no flakiness.
"""

from .controller import DEGRADE_ACTIONS, DegradationController
from .errors import (AdmissionError, Cancelled, CapacityError,
                     ConfigurationError, DeadlineExceeded, HandoffError,
                     KVCacheStateError, QueueOverflow, ReplicaUnavailable,
                     SequenceStateError, ServingError, StepFailure)
from .faults import FAULT_POINTS, FAULTS, FaultInjector, InjectedFault
from .preemption import PREEMPTION_POLICIES, Preempted, pick_victim

__all__ = [
    "ServingError", "AdmissionError", "CapacityError", "ConfigurationError",
    "DeadlineExceeded", "KVCacheStateError", "SequenceStateError",
    "StepFailure", "QueueOverflow", "Cancelled",
    "ReplicaUnavailable", "HandoffError",
    "FAULTS", "FAULT_POINTS", "FaultInjector", "InjectedFault",
    "Preempted", "PREEMPTION_POLICIES", "pick_victim",
    "DEGRADE_ACTIONS", "DegradationController",
]
