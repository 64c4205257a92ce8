"""Typed failure hierarchy for the serving surface.

Everything the engine adapters and the paged KV cache manager raise at
their public boundaries derives from :class:`ServingError`, so an engine
can catch the whole family with one clause and branch on type to pick a
recovery: re-queue (:class:`CapacityError`), reject the request
(:class:`AdmissionError`), drop it (:class:`DeadlineExceeded`), or retry
the step (:class:`StepFailure` — host state is rolled back before it
propagates).

Each class also subclasses the builtin it replaced (``ValueError`` /
``RuntimeError`` / ``TimeoutError``) so pre-hierarchy callers written
against the old ad-hoc raises keep working unchanged.
"""

from __future__ import annotations

from typing import Sequence, Tuple

__all__ = [
    "ServingError", "AdmissionError", "SequenceStateError",
    "ConfigurationError", "CapacityError", "KVCacheStateError",
    "DeadlineExceeded", "StepFailure", "QueueOverflow", "Cancelled",
    "ReplicaUnavailable", "HandoffError",
]


class ServingError(Exception):
    """Base of the serving failure hierarchy. :attr:`seq_ids` carries the
    affected sequence ids when the failure is attributable to specific
    rows (empty otherwise), so engines never have to parse messages.

    :attr:`trace_id` is the flight-recorder event id of the matching
    ``error.*`` timeline event when the recorder was enabled at raise time
    (``telemetry.trace``), ``None`` otherwise — a post-mortem dump can
    jump from the caught exception straight to its place in the trace."""

    trace_id = None                    # set by FlightRecorder.error()

    def __init__(self, msg: str, seq_ids: Sequence[int] = ()):
        super().__init__(msg)
        self.seq_ids: Tuple[int, ...] = tuple(seq_ids)


class AdmissionError(ServingError, ValueError):
    """``add_requests`` arguments are invalid: empty/duplicate seq_ids,
    zero-length or over-long prompts, seq_id already running or out of
    range. Nothing was admitted; no device or cache state changed."""


class SequenceStateError(ServingError, ValueError):
    """An operation addressed a seq_id in the wrong lifecycle state
    (e.g. ``step()`` on a released or never-added id)."""


class ConfigurationError(ServingError, ValueError):
    """The adapter was built over an incompatibly-configured application."""


class CapacityError(ServingError, RuntimeError):
    """A bounded resource ran out: KV cache blocks, batch slots, or the
    compiled ``seq_len``. The failed call was rolled back (or, with a
    preemption policy armed, lower-priority sequences were evicted first —
    a ``CapacityError`` then means eviction could not free enough)."""


class KVCacheStateError(ServingError, RuntimeError):
    """KV-cache bookkeeping invariant violated (double free, shrink below
    zero). Indicates a caller bug, not load — never retry."""


class DeadlineExceeded(ServingError, TimeoutError):
    """One or more sequences blew their per-request wall-clock budget.

    Raised by ``step()`` BEFORE any device work: the engine should
    ``release(exc.seq_ids)`` (or re-queue with a fresh deadline) and step
    again. Carries the offending ids in :attr:`seq_ids`."""


class QueueOverflow(CapacityError):
    """The serving engine's request queue is at ``max_queue_depth``:
    admission control rejected the submit before it consumed any engine
    or device state. A load balancer should shed or retry elsewhere.
    Subclasses :class:`CapacityError` so capacity-aware callers handle
    both with one clause."""


class ReplicaUnavailable(CapacityError):
    """The fleet router has no replica able to take the request: every
    replica is draining or dead (or the one a caller targeted is). A load
    balancer should shed or retry elsewhere. Subclasses
    :class:`CapacityError` — like :class:`QueueOverflow` it is a
    load-shedding signal, not a caller bug."""


class HandoffError(ServingError, RuntimeError):
    """A disaggregated prefill→decode handoff failed: malformed or
    wrong-schema record, capture of a sequence in the wrong lifecycle
    state, or a decode-side admission that could not consume the record.
    The failing side's engine state is unchanged (capture reads before it
    releases; admission is transactional)."""


class Cancelled(ServingError):
    """The request was cancelled (explicit ``cancel()`` call or the
    streaming client went away). Queued entries are dropped without any
    device work; running sequences are released and their KV blocks
    reclaimed. Delivered tokens remain valid."""


class StepFailure(ServingError, RuntimeError):
    """A device step (prefill or decode) raised. Host-side adapter and
    cache-manager bookkeeping was rolled back to the pre-call state before
    this propagates. The original exception rides along as ``__cause__``;
    :attr:`phase` is ``"prefill"`` or ``"decode"``; :attr:`seq_ids` names
    the rows in the failed call.

    :attr:`retry_safe` is True when the failure happened before the
    device computation consumed (donated) the KV cache — injected faults
    and host-side errors — so the engine may simply retry the call. When
    False, a genuine device failure surfaced after dispatch: the donated
    cache buffers are gone, device state is lost, and the adapter (and
    its application) must be rebuilt before serving can continue."""

    def __init__(self, msg: str, phase: str = "",
                 seq_ids: Sequence[int] = (), retry_safe: bool = True):
        super().__init__(msg, seq_ids)
        self.phase = phase
        self.retry_safe = retry_safe
