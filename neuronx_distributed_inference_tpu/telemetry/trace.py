"""Flight recorder — a bounded, lock-guarded ring of structured trace
events, the "what happened, in what order" layer on top of the metrics
registry ("how much / how often").

Every event carries a monotonic ``perf_counter()`` timestamp, a stable
``name``, a category lane (``engine`` — scheduler pass phases, ``adapter``
— dispatch/fetch boundaries, ``app`` — ``_run_*`` compile/execute,
``error`` — typed failures) and structured ``args`` (request / tenant /
seq_id labels). Two pure exporters:

  * :meth:`FlightRecorder.to_chrome` — Chrome trace-event JSON
    (``chrome://tracing`` / Perfetto loadable: ``traceEvents`` with
    ``ph="X"`` complete slices and ``ph="i"`` instants, one ``tid`` lane
    per category, timestamps in microseconds from the recorder epoch);
  * :meth:`FlightRecorder.to_jsonl` — one JSON object per line, for
    grep/jq post-mortems.

Event **names are a stable contract** exactly like the metric names in
``metrics.py`` — dashboards, the post-mortem tooling, and the golden test
(``tests/test_flight_recorder.py``) key on them; renames are breaking.
The canonical set lives in :data:`ENGINE_PASS_PHASES` /
:data:`ADAPTER_EVENTS` / :data:`APP_EVENTS`.

A span that ran long leaves a record: where a slice closes at
:data:`STALL_SECONDS` or more, less the stalls already counted inside it,
it is a stall of the INNERMOST such span — counted in
``nxdi_host_stall_seconds_total{span}`` / ``nxdi_host_stalls_total{span}``
and copied, with the names open around it, into a list of at most
:data:`STALL_RECORDS` that the ring's wrap does not evict
(:meth:`FlightRecorder.stalls`). The four ``pass.*`` phases and
``loop.idle`` are never stalls: they are as long as their work, or as the
quiet, is.

One timeline with the profiler: while the recorder is enabled every
:meth:`FlightRecorder.span` also enters a ``jax.profiler.TraceAnnotation``
of the same name (stat ``pass_id``), so inside a ``jax.profiler`` session
each slice is a TraceMe event on the ``/host:CPU`` plane of the same
``.xplane.pb`` as the device events — the ring can wrap, the profiler's
file holds its whole session. And where a slice closes
(:meth:`FlightRecorder.complete`) its duration is added to
``nxdi_host_seconds_total{span=<name>,under=<parent>}`` when a metrics
registry is live: host seconds per span over any window, read from two
registry snapshots. ``under`` is the span that was open around it on the
same thread ("" at the top), so a reader gets a span's SELF time as its
own seconds minus those recorded under it — e.g. ``run.paged`` closes
under ``dispatch.prefill_chunk`` for a prompt's chunk and under
``pass.dispatch`` for a decode step (under the serving engine both are
dispatched in that stage; a direct blocking ``add_requests`` runs its chunks
wherever its caller stands); the label tells them apart, names alone cannot. The
``request.*`` slices (:data:`TRACE_EVENTS`) are the exception: a request's
seconds, not the thread's, so they reach the ring and nothing else
(:data:`REQUEST_CAT`); two boundaries of a request's timeline are
zero-length TraceMe marks instead (:meth:`FlightRecorder.mark`).

Disabled by default with the PR-1 zero-cost contract: the module-global
recorder is a shared no-op (:data:`NULL_RECORDER`); instrumented call
sites pay one attribute check (``rec.enabled``) and never touch device
state — recording can change neither jit cache keys nor token streams
(pinned bit-identical by ``tests/test_flight_recorder.py``); the disabled
recorder imports nothing from ``jax``. When the ring
wraps, dropped events are counted (:attr:`FlightRecorder.dropped` plus the
``nxdi_trace_events_dropped_total{ring="trace"}`` counter when a live
metrics registry is installed) so a post-mortem states its own truncation
instead of silently starting mid-story.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from typing import Any, Dict, List, Optional

from .registry import get_registry

__all__ = [
    "ENGINE_PASS_PHASES", "ENGINE_EVENTS", "ADAPTER_EVENTS", "APP_EVENTS",
    "LOOP_EVENTS", "FLEET_EVENTS", "DEGRADE_EVENTS", "WARMUP_EVENTS",
    "REQUEST_CAT",
    "EVENT_NAMES", "STALL_SECONDS", "STALL_RECORDS",
    "FlightRecorder", "NullFlightRecorder", "NULL_RECORDER",
    "get_recorder", "set_recorder", "enable_recorder", "disable_recorder",
]

#: Engine scheduling-pass phases, one complete slice per ``run_pass``
#: stage (serving/engine/scheduler.py). STABLE names.
ENGINE_PASS_PHASES = ("pass.expire", "pass.preempt", "pass.admit",
                      "pass.dispatch")

#: The serving loop's time OUTSIDE a pass (``ServingEngine.run_forever``):
#: with the four pass phases these partition the loop thread's time.
#:   ``loop.yield``   ``await asyncio.sleep(0)`` with work pending: SSE
#:                    writers, HTTP reads, whoever else holds the GIL
#:   ``loop.idle``    the idle nap (nothing queued or running)
LOOP_EVENTS = ("loop.yield", "loop.idle")

#: Other engine-lane events (serving/engine/scheduler.py). STABLE names.
#:   ``stream.deliver``         tokens routed to request streams
#:   ``admission.headroom``     the scheduler hit a capacity reject/stall;
#:                              carries the adapter's live admission-
#:                              headroom estimate (free_blocks,
#:                              headroom_tokens, free_slots)
#:   ``deliver.tokens``         a pass's tokens routed to their streams,
#:                              with the finishes and releases that
#:                              follow (a slice, under ``pass.dispatch``;
#:                              ``stream.deliver`` is its instant)
ENGINE_EVENTS = ("stream.deliver", "admission.headroom", "deliver.tokens")

#: Adapter boundary events (serving/adapter.py + serving/ragged/path.py).
#: STABLE names.
#:   ``dispatch.decode``        one decode dispatch (eager or pipelined)
#:   ``dispatch.decode_loop``   one fused step_many(k) dispatch
#:   ``dispatch.prefill_chunk`` one packed prefill-chunk dispatch
#:   ``dispatch.ragged``        THE unified mixed dispatch of a ragged
#:                              engine step (serving/ragged/; carries
#:                              per-row ``seq_ids`` and ``traces``)
#:   ``fetch.tokens``           a blocking device->host token fetch
#:   ``preempt``                one sequence evicted (any reason)
#:   ``dispatch.build``         a decode dispatch's host inputs: growing
#:                              the block tables, the scratch and its
#:                              fill, the fed-back tokens (a slice, under
#:                              ``pass.dispatch``)
#:   ``dispatch.retire``        one decode step's tokens made host-visible
#:                              and booked: ``fetch.tokens`` closes under
#:                              it, its self time is the token append, the
#:                              stop checks and the telemetry hooks
ADAPTER_EVENTS = ("dispatch.decode", "dispatch.decode_loop",
                  "dispatch.prefill_chunk", "dispatch.ragged",
                  "fetch.tokens", "preempt", "dispatch.build",
                  "dispatch.retire")

#: Application events (models/application.py). STABLE names.
#:   ``run.<kind>``   host window of one _run_* call (entry -> dispatch
#:                    return, RNG split included; asynchronous — excludes
#:                    device wait, which is ``fetch.tokens``). One of the
#:                    eight kinds below and nothing else starts with
#:                    ``run.``: a reader may sum the prefix
#:   ``prep.inputs``  of ``run.paged``: entry to the last host->device
#:                    placement (ids, sampling params, seeds, positions,
#:                    slot mapping, block table, last_idx, state slots)
#:   ``prep.rng``     of ``run.paged``: the RNG split (two helper programs)
#:   ``prep.enqueue`` of ``run.paged``: the jit call to the return of its
#:                    asynchronous dispatch
#:   ``compile``      first-time (kind, bucket, shape) graph build
APP_EVENTS = ("run.prefill", "run.decode", "run.decode_loop", "run.paged",
              "run.paged_loop", "run.ragged", "run.spec_draft",
              "run.spec_verify", "compile", "prep.inputs", "prep.rng",
              "prep.enqueue")

#: Fleet-layer events (serving/fleet/). STABLE names.
#:   ``fleet.route``    one request routed to a replica (request_id,
#:                      replica, warmth, affinity)
#:   ``fleet.drain``    a replica transitioned to draining/dead
#:                      (replica, state, reason)
#:   ``kv.spill``       one block payload spilled to the host-RAM tier
#:   ``kv.restore``     spilled block payloads restored to device at
#:                      admission (seq_id, blocks, tokens)
#:   ``handoff.send``   a prefill-role engine captured a handoff record
#:   ``handoff.recv``   a decode-role engine admitted a handoff record
#:   ``fleet.all_dead`` the LAST healthy replica left rotation — the
#:                      operator page (replica, reason, in_flight)
#:   ``fleet.scale_up`` the FleetAutoscaler added a replica (replica,
#:                      reason, n_compiles, queue, burn, free_slots)
#:   ``fleet.scale_down`` the FleetAutoscaler started retiring a replica
#:                      (replica, reason, migrated, queue, burn)
FLEET_EVENTS = ("fleet.route", "fleet.drain", "kv.spill", "kv.restore",
                "handoff.send", "handoff.recv", "fleet.all_dead",
                "fleet.scale_up", "fleet.scale_down")

#: Degradation-controller events (resilience/controller.py). STABLE
#: names; both carry ``tenant``, ``action`` and the deciding ``burn``.
#:   ``degrade.enter``  an action engaged (burn crossed the enter
#:                      threshold in BOTH windows)
#:   ``degrade.exit``   an action released (burn below the exit
#:                      threshold after the minimum hold)
DEGRADE_EVENTS = ("degrade.enter", "degrade.exit")

#: Request-trace lifecycle events (telemetry/request_trace.py +
#: serving/engine/scheduler.py + serving/fleet/router.py). STABLE names.
#: Every one carries ``trace`` — the request's stable trace id
#: (``meta["trace"]``), which also rides ``Preempted``/handoff records
#: across replicas so a continuation stitches onto the same trace.
#:   ``trace.begin``    frontend/router/engine ingress (request_id,
#:                      tenant, prompt_len, deadline_s)
#:   ``trace.admit``    the request left the queue into one transactional
#:                      admission (seq_id, wait_s)
#:   ``trace.requeue``  the request went back to a queue — preemption or
#:                      replica failover (reason, replica when fleet)
#:   ``trace.emit``     terminal emission (reason, n_tokens)
#: and the request's time to first token as five complete slices, laid out
#: at its first SSE write from the stamps of its ``RequestTimeline``
#: (telemetry/request_trace.py ``TIMELINE_PHASES``; ``request_id``):
#:   ``request.accept``        connection accepted -> ``submit``
#:   ``request.queue``         ``submit`` -> picked for admission
#:   ``request.prefill_wait``  -> its first chunk enqueued
#:   ``request.prefill``       -> its first token host-visible
#:   ``request.write``         -> the ``writer.write`` of its first event
#: These are a REQUEST's seconds, not the thread's (:data:`REQUEST_CAT`).
TRACE_EVENTS = ("trace.begin", "trace.admit", "trace.requeue",
                "trace.emit", "request.accept", "request.queue",
                "request.prefill_wait", "request.prefill", "request.write")

#: Slices of this category are a request's time, laid out after the fact:
#: they overlap each other and whatever the thread was doing, so they are
#: recorded and nothing else — no ``nxdi_host_seconds_total``, no parent
#: (a reader's self time of ``loop.yield`` is not theirs to eat), no stall.
REQUEST_CAT = "request"

#: Cold-start / steady-state compile events (serving/warmup.py +
#: models/application.py). STABLE names.
#:   ``compile.unexpected``  a graph build AFTER precompile() declared
#:                           steady state — a tracked incident (kind,
#:                           bucket, sig, plus ``traces`` = the request
#:                           trace ids packed into the triggering
#:                           dispatch, so the incident lands on the
#:                           victims' trace lanes)
WARMUP_EVENTS = ("compile.unexpected",)

EVENT_NAMES = (ENGINE_PASS_PHASES + LOOP_EVENTS + ENGINE_EVENTS
               + ADAPTER_EVENTS + APP_EVENTS + FLEET_EVENTS + TRACE_EVENTS
               + DEGRADE_EVENTS + WARMUP_EVENTS)
_EVENT_SET = frozenset(EVENT_NAMES)

#: A slice this long (seconds) is a stall; the longest legitimate single
#: dispatch the benchmark's cells make is a 0.90 s prefill pack.
STALL_SECONDS = 2.0
#: How many stalls the recorder keeps beside its ring.
STALL_RECORDS = 64
#: Never stalls: a pass phase is as long as the work inside it (which is
#: what gets named), the idle nap as long as the quiet.
_NEVER_STALLS = frozenset(ENGINE_PASS_PHASES + ("loop.idle",))

#: Category -> Chrome trace tid lane (deterministic ordering in the UI).
_CAT_TIDS = {"engine": 1, "adapter": 2, "app": 3, "error": 4, "fleet": 5,
             "request": 6}


class _TraceSpan:
    """Context manager handed out by :meth:`FlightRecorder.span`: records
    one complete event over the ``with`` body, and is a profiler
    ``TraceAnnotation`` of the same name while it is open. ``set()`` adds
    args that are only known inside the body (they reach the recorded
    event, not the annotation)."""

    __slots__ = ("_rec", "name", "_cat", "_args", "_t0", "_ann", "_parent",
                 "stalled_s")

    def __init__(self, rec: "FlightRecorder", name: str, cat: str,
                 args: Dict[str, Any]):
        self._rec = rec
        self.name = name
        self._cat = cat
        self._args = args
        #: seconds of stalls already counted inside this span
        self.stalled_s = 0.0

    def set(self, **args) -> None:
        self._args.update(args)

    def __enter__(self) -> "_TraceSpan":
        rec = self._rec
        stack = rec._open_spans()
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self._ann = rec._annotate(self.name, self._args)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = self._rec._open_spans()
        # LIFO but for spans held open across an ``await`` (loop.yield /
        # loop.idle of two engines on one loop)
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is self:
                del stack[i]
                break
        self._rec._close(self.name, self._t0, t1, self._cat, self._args,
                         self._parent, self.stalled_s)


class FlightRecorder:
    """Bounded ring of structured events (see module docstring)."""

    enabled = True

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.epoch = time.perf_counter()   # chrome ts origin
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()
        self._ids = itertools.count()
        self.dropped = 0
        self._dropped_flushed = 0      # high-water mark already counted
        #: sequence number of the scheduling pass in progress (None before
        #: the first): every span opened under it carries it as ``pass_id``
        self.pass_id: Optional[int] = None
        self._local = threading.local()    # per-thread stack of open spans
        #: slices that ran :data:`STALL_SECONDS` or longer, oldest first;
        #: beside the ring, so its wrap does not evict them
        self._stalls: List[Dict[str, Any]] = []
        #: the live registry and its ``nxdi_host_seconds_total`` series
        #: adders by ``(span, under)``: one dict lookup where a slice closes
        self._host_seconds: tuple = (None, {})
        try:                       # the ENABLED recorder alone touches jax
            from jax.profiler import TraceAnnotation
        except ImportError:        # pragma: no cover - jax is a hard dep
            TraceAnnotation = None
        self._trace_annotation = TraceAnnotation

    def next_pass(self) -> int:
        """Open the next scheduling pass (``ServingEngine.run_pass``)."""
        self.pass_id = 0 if self.pass_id is None else self.pass_id + 1
        return self.pass_id

    def _open_spans(self) -> List[_TraceSpan]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _annotate(self, name: str, args: Dict[str, Any]):
        """Enter a profiler TraceMe for a span (a cheap no-op outside a
        ``jax.profiler`` session); returns it for the matching exit."""
        if self._trace_annotation is None:
            return None
        pid = args.get("pass_id")
        ann = (self._trace_annotation(name) if pid is None
               else self._trace_annotation(name, pass_id=pid))
        ann.__enter__()
        return ann

    # -- recording ---------------------------------------------------------
    def _push(self, ev: Dict[str, Any]) -> str:
        with self._lock:
            eid = ev["id"] = f"e{next(self._ids)}"
            self._events.append(ev)
            excess = len(self._events) - self.capacity
            if excess > 0:
                del self._events[:excess]
                self.dropped += excess
        return eid

    def _flush_drops(self) -> None:
        """Report accumulated ring evictions to the metrics registry.
        Deferred off the per-event hot path (once the ring is full EVERY
        push evicts) onto the read/export surfaces, where the count is
        actually consumed.

        Accounting is delta-against-a-high-water-mark, serialized by its
        own lock: concurrent ``tail()``/``events()`` exports each flush
        exactly the drops no other flush has claimed yet (never the same
        delta twice), and a flush while the registry is disabled counts
        NOTHING as flushed — the drops are reported, not lost, once a
        live registry is back. Invariant (regression-pinned):
        ``nxdi_trace_events_dropped_total{ring="trace"}`` on one live
        registry equals ``self.dropped`` after any export."""
        reg = get_registry()
        if not reg.enabled:
            return                 # deferred, not discarded
        with self._flush_lock:
            with self._lock:
                n = self.dropped - self._dropped_flushed
                self._dropped_flushed += n
            if n:
                from . import metrics as tmetrics
                tmetrics.trace_events_dropped_counter(reg).inc(n,
                                                               ring="trace")

    def instant(self, name: str, cat: str = "engine", **args) -> str:
        """Record a point-in-time event; returns its event id."""
        return self._push({"name": name, "cat": cat, "ph": "i",
                           "ts": time.perf_counter(), "args": args})

    def complete(self, name: str, t0: float, cat: str = "engine",
                 t1: Optional[float] = None, **args) -> str:
        """Record a complete slice spanning ``[t0, t1]`` (``t1`` defaults
        to now); returns its event id."""
        if t1 is None:
            t1 = time.perf_counter()
        stack = self._open_spans()
        return self._close(name, t0, t1, cat, args,
                           stack[-1] if stack else None)

    def _close(self, name: str, t0: float, t1: float, cat: str,
               args: Dict[str, Any], parent: Optional[_TraceSpan],
               stalled_s: float = 0.0) -> str:
        """The ONE place a slice's duration is known: record it, count its
        host seconds by span and parent (label sets bounded by the stable
        names), and keep a slice that ran long (module docstring).
        ``parent`` is the span open around it on this thread,
        ``stalled_s`` the stalls already counted inside it. A slice of
        :data:`REQUEST_CAT` is recorded and that is all."""
        dur = t1 - t0
        if cat == REQUEST_CAT:
            return self._push({"name": name, "cat": cat, "ph": "X",
                               "ts": t0, "dur": dur, "args": args})
        reg = get_registry()
        if reg.enabled:
            span = name if name in _EVENT_SET else "other"
            under = (parent.name if parent is not None
                     and parent.name in _EVENT_SET else "")
            of, adders = self._host_seconds
            if of is not reg:
                adders = {}
                self._host_seconds = (reg, adders)
            add = adders.get((span, under))
            if add is None:
                from . import metrics as tmetrics
                add = adders[(span, under)] = tmetrics.host_seconds_counter(
                    reg).child(span=span, under=under)
            add(max(dur, 0.0))
        if dur - stalled_s >= STALL_SECONDS and name not in _NEVER_STALLS:
            self._note_stall(reg, name, t0, dur, dur - stalled_s, args)
            stalled_s = dur
        if parent is not None:
            parent.stalled_s += stalled_s
        return self._push({"name": name, "cat": cat, "ph": "X",
                           "ts": t0, "dur": dur, "args": args})

    def _note_stall(self, reg, name: str, t0: float, dur: float,
                    own_s: float, args: Dict[str, Any]) -> None:
        """``own_s``: the slice's seconds that no stall inside it was
        already counted for (all of it, for the innermost)."""
        around = [s.name for s in self._open_spans()]
        with self._lock:
            self._stalls.append({"name": name, "ts": t0, "dur": dur,
                                 "args": dict(args), "around": around})
            del self._stalls[:-STALL_RECORDS]
        if reg.enabled:
            from . import metrics as tmetrics
            span = name if name in _EVENT_SET else "other"
            tmetrics.host_stall_seconds_counter(reg).inc(own_s, span=span)
            tmetrics.host_stalls_counter(reg).inc(span=span)

    def span(self, name: str, cat: str = "engine", **args) -> _TraceSpan:
        """``with rec.span("pass.admit"): ...`` — one complete event over
        the body, a profiler TraceMe while it is open, tagged with the
        ``pass_id`` of the scheduling pass in progress."""
        if self.pass_id is not None:
            args.setdefault("pass_id", self.pass_id)
        return _TraceSpan(self, name, cat, args)

    def mark(self, name: str, trace: Optional[str]) -> None:
        """A zero-length profiler TraceMe ``name`` (stat ``trace``): inside
        a ``jax.profiler`` session a boundary of a request's timeline lies
        on ``/host:CPU`` of the same xplane as the device's operations.
        Nothing reaches the ring: the request's slices carry the instant."""
        if self._trace_annotation is not None:
            with self._trace_annotation(name, trace=trace or ""):
                pass

    def error(self, err: BaseException, cat: str = "error", **args):
        """Record a typed failure as an ``error.<Type>`` instant event
        (message, seq_ids, phase/retry_safe when present) and attach the
        event id to the exception as ``err.trace_id`` so a post-mortem
        can jump from the raised error to its place in the timeline.
        Returns ``err`` for ``raise rec.error(...)`` chaining."""
        attrs: Dict[str, Any] = {
            "message": str(err),
            "seq_ids": [int(s) for s in getattr(err, "seq_ids", ()) or ()],
        }
        phase = getattr(err, "phase", None)
        if phase:
            attrs["phase"] = phase
        retry_safe = getattr(err, "retry_safe", None)
        if retry_safe is not None:
            attrs["retry_safe"] = bool(retry_safe)
        attrs.update(args)
        eid = self.instant(f"error.{type(err).__name__}", cat=cat, **attrs)
        try:
            err.trace_id = eid
        except Exception:                  # frozen/slotted carriers
            pass
        return err

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._stalls.clear()
            self.dropped = 0
            self._dropped_flushed = 0

    # -- reading -----------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        self._flush_drops()
        with self._lock:
            return [dict(e) for e in self._events]

    def tail(self, n: int = 256) -> List[Dict[str, Any]]:
        """The newest ``n`` events (post-mortem dump payload)."""
        self._flush_drops()
        with self._lock:
            return [dict(e) for e in self._events[-n:]]

    def stalls(self) -> List[Dict[str, Any]]:
        """The slices that ran long (at most :data:`STALL_RECORDS`, oldest
        first): ``name``, ``ts``, ``dur``, ``args`` and ``around``, the
        names open around each, outermost first."""
        with self._lock:
            return [dict(e) for e in self._stalls]

    def __len__(self) -> int:
        return len(self._events)

    # -- exporters (pure) --------------------------------------------------
    def to_chrome(self, events: Optional[List[Dict[str, Any]]] = None
                  ) -> Dict[str, Any]:
        """Chrome trace-event JSON (load in ``chrome://tracing`` or
        Perfetto). Timestamps are microseconds from the recorder epoch;
        each category gets its own named thread lane."""
        if events is None:
            events = self.events()
        out: List[Dict[str, Any]] = []
        cats = sorted({e["cat"] for e in events},
                      key=lambda c: _CAT_TIDS.get(c, 99))
        for cat in cats:
            out.append({"name": "thread_name", "ph": "M", "pid": 1,
                        "tid": _CAT_TIDS.get(cat, 99),
                        "args": {"name": f"nxdi.{cat}"}})
        for e in events:
            ce: Dict[str, Any] = {
                "name": e["name"], "cat": e["cat"], "ph": e["ph"],
                "ts": (e["ts"] - self.epoch) * 1e6,
                "pid": 1, "tid": _CAT_TIDS.get(e["cat"], 99),
                "args": {**e["args"], "id": e["id"]},
            }
            if e["ph"] == "X":
                ce["dur"] = e["dur"] * 1e6
            else:
                ce["s"] = "t"          # instant scope: thread
            out.append(ce)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def to_jsonl(self, events: Optional[List[Dict[str, Any]]] = None) -> str:
        """One JSON object per line (grep/jq-friendly), timestamps kept in
        raw ``perf_counter()`` seconds."""
        if events is None:
            events = self.events()
        return "\n".join(json.dumps(e, sort_keys=True) for e in events)


class NullFlightRecorder:
    """Disabled recorder: every method is a no-op; the library default."""

    enabled = False
    capacity = 0
    epoch = 0.0
    dropped = 0

    _NULL_SPAN = None                  # set below (shared instance)

    def instant(self, name, cat="engine", **args):
        return ""

    def complete(self, name, t0, cat="engine", t1=None, **args):
        return ""

    def span(self, name, cat="engine", **args):
        return self._NULL_SPAN

    def mark(self, name, trace):
        pass

    def error(self, err, cat="error", **args):
        return err

    def clear(self):
        pass

    def events(self):
        return []

    def tail(self, n=256):
        return []

    def stalls(self):
        return []

    def __len__(self):
        return 0

    def to_chrome(self, events=None):
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "otherData": {"dropped_events": 0}}

    def to_jsonl(self, events=None):
        return ""


class _NullSpanCM:
    __slots__ = ()

    def set(self, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


NullFlightRecorder._NULL_SPAN = _NullSpanCM()

NULL_RECORDER = NullFlightRecorder()
_global_recorder: Any = NULL_RECORDER


def get_recorder():
    """The process-global flight recorder (a no-op unless
    :func:`enable_recorder`'d or :func:`set_recorder`'d)."""
    return _global_recorder


def set_recorder(rec) -> None:
    global _global_recorder
    _global_recorder = rec if rec is not None else NULL_RECORDER


def enable_recorder(capacity: int = 4096) -> FlightRecorder:
    """Swap a live recorder into the global slot (idempotent; an existing
    live recorder is kept regardless of ``capacity``)."""
    global _global_recorder
    if not isinstance(_global_recorder, FlightRecorder):
        _global_recorder = FlightRecorder(capacity)
    return _global_recorder


def disable_recorder() -> None:
    global _global_recorder
    _global_recorder = NULL_RECORDER
