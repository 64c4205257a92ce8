"""Closed-loop fleet autoscaler — elastic replica count (ISSUE 17).

A :class:`FleetAutoscaler` is attached to an
:class:`~.router.EngineRouter` (``autoscaler=``) and consulted exactly
once per :meth:`~.router.EngineRouter.run_pass`. Each evaluation reads
three fleet-aggregated signals:

  * **queue pressure** — mean engine queue depth per HEALTHY replica
    (the same ``ServingEngine.load`` tuple the router's routing key
    reads);
  * **SLO burn** — the max multiwindow
    :meth:`~...telemetry.slo.SLOTracker.burn_index` across every
    replica that carries a tracker (replicas without one contribute
    0.0, so virtual-clock benches never mix clock domains);
  * **admission headroom** — the min
    :func:`~..warmup.admission_headroom` ``free_slots`` across HEALTHY
    replicas (slots, not blocks: a fleet can be block-rich and still
    reject on batch slots).

and then applies the same hysteresis discipline as the
:class:`~...resilience.controller.DegradationController`: *enter* and
*exit* thresholds live on opposite sides of a dead band (validated at
construction), a signal must HOLD past ``min_hold_s`` before any
action, and every action opens a ``cooldown_s`` window during which
nothing else may fire — so a noisy boundary cannot flap the fleet.

**Scale-up is precompile-first.** The injectable ``replica_factory``
builds the engine; the autoscaler then walks
:func:`~..warmup.precompile` against the process's shared persistent
compilation cache and only admits the replica
(:meth:`~.router.EngineRouter.add_replica`) if the report says
``n_compiles == 0`` — a replica that would compile under traffic is
closed and rejected instead (``stats["rejected_cold"]``), because a
compile stall behind live decode traffic is exactly the latency cliff
the warmup plane exists to prevent.

**Scale-down is two-phase.** Initiate: pick the least-loaded
self-spawned (else least-loaded healthy) replica above
``min_replicas`` and ``drain(mode="migrate")`` it — running streams
move to survivors carrying their KV, nothing recomputes. Reap: on
later evaluations, once the victim holds no fleet-bound requests and
no engine work, :meth:`~.router.EngineRouter.remove_replica` drops it
(closing the engine if this autoscaler spawned it).

Every evaluation refreshes the ``nxdi_fleet_replicas{state}`` gauge
and every action lands on the flight recorder (``fleet.scale_up`` /
``fleet.scale_down``). The whole evaluation is a fault point
(``autoscale``): an injected trip aborts it with the fleet unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

from ...resilience.errors import ConfigurationError
from ...resilience.faults import FAULTS as _FAULTS
from ...resilience.faults import InjectedFault
from ...telemetry import get_registry
from ...telemetry import metrics as tmetrics
from ...telemetry.trace import get_recorder as _get_recorder
from .router import DEAD, HEALTHY

__all__ = ["FleetAutoscaler"]


class FleetAutoscaler:
    """Closed-loop replica-count controller (see module docstring).

    ``replica_factory`` is a zero-arg callable returning a new engine,
    a ``(name, engine)`` pair, or ``(name, engine, registry)`` — names
    default to ``auto0..N``; a registry is auto-created when the router
    scopes per-replica registries and the factory supplies none.

    Enter/exit threshold pairs must leave a dead band (exit strictly
    calmer than enter) or construction raises
    :class:`~...resilience.errors.ConfigurationError` — the same
    construction-time validation discipline as
    :func:`~...resilience.controller.check_policy`.
    """

    def __init__(self, replica_factory: Callable[[], Any], *,
                 min_replicas: int = 1, max_replicas: int = 4,
                 queue_enter: float = 8.0, queue_exit: float = 2.0,
                 burn_enter: float = 1.0, burn_exit: float = 0.25,
                 headroom_enter_slots: int = 0,
                 headroom_exit_slots: int = 2,
                 min_hold_s: float = 0.0, cooldown_s: float = 1.0,
                 min_interval_s: float = 0.0,
                 now_fn: Callable[[], float] = time.perf_counter):
        if not callable(replica_factory):
            raise ConfigurationError(
                "replica_factory must be a zero-arg callable returning "
                "an engine, (name, engine), or (name, engine, registry)")
        if min_replicas < 1 or max_replicas < min_replicas:
            raise ConfigurationError(
                "need 1 <= min_replicas <= max_replicas "
                f"(got {min_replicas}..{max_replicas})")
        if queue_exit >= queue_enter:
            raise ConfigurationError(
                f"queue_exit ({queue_exit}) must be < queue_enter "
                f"({queue_enter}) — no dead band means flapping")
        if burn_exit >= burn_enter:
            raise ConfigurationError(
                f"burn_exit ({burn_exit}) must be < burn_enter "
                f"({burn_enter}) — no dead band means flapping")
        if headroom_exit_slots <= headroom_enter_slots:
            raise ConfigurationError(
                f"headroom_exit_slots ({headroom_exit_slots}) must be > "
                f"headroom_enter_slots ({headroom_enter_slots}) — no "
                "dead band means flapping")
        if min_hold_s < 0 or cooldown_s < 0 or min_interval_s < 0:
            raise ConfigurationError(
                "min_hold_s, cooldown_s and min_interval_s must be >= 0")
        self.replica_factory = replica_factory
        self.min_replicas = min_replicas
        self.max_replicas = max_replicas
        self.queue_enter = float(queue_enter)
        self.queue_exit = float(queue_exit)
        self.burn_enter = float(burn_enter)
        self.burn_exit = float(burn_exit)
        self.headroom_enter_slots = int(headroom_enter_slots)
        self.headroom_exit_slots = int(headroom_exit_slots)
        self.min_hold_s = float(min_hold_s)
        self.cooldown_s = float(cooldown_s)
        self.min_interval_s = float(min_interval_s)
        self._now = now_fn
        self._next_eval = 0.0
        self._hot_since: Optional[float] = None
        self._calm_since: Optional[float] = None
        self._cooldown_until = 0.0
        self._spawn_counter = 0
        self._spawned: set = set()       # replica names this controller made
        self._retiring: Dict[str, bool] = {}   # name -> self-spawned?
        self.stats: Dict[str, int] = {
            "evaluations": 0, "scale_ups": 0, "scale_downs": 0,
            "reaped": 0, "rejected_cold": 0, "aborted": 0}
        #: action timeline, bounded: [{"t", "action", "replica", ...}, ...]
        self.history: List[Dict[str, Any]] = []

    # -- signals -----------------------------------------------------------
    def signals(self, router) -> Dict[str, float]:
        """The three fleet-aggregated inputs of one evaluation, also
        served to the bench report: mean queue depth per healthy
        replica, max merged SLO burn, min free batch slots."""
        from ..warmup import admission_headroom
        healthy = [rep for rep in router.replicas.values()
                   if rep.state == HEALTHY
                   and not getattr(rep.engine, "closed", False)]
        queues, burns, slots = [], [0.0], []
        for rep in healthy:
            load = getattr(rep.engine, "load", None)
            if load is None:
                ds = rep.engine.debug_state()
                load = (ds["queue"]["depth"], len(ds["active"]))
            queues.append(float(load[0]))
            slo = getattr(rep.engine, "slo", None)
            if slo is not None:
                burns.extend(slo.burn_index().values())
            try:
                slots.append(
                    float(admission_headroom(rep.engine.adapter)
                          .get("free_slots", 0)))
            except Exception:
                pass                   # replica died mid-signal: skip it
        n = len(healthy)
        return {
            "healthy": float(n),
            "queue": (sum(queues) / n) if n else 0.0,
            "burn": max(burns),
            "free_slots": min(slots) if slots else 0.0,
        }

    # -- evaluation --------------------------------------------------------
    def update(self, router) -> Optional[str]:
        """One closed-loop evaluation (called by ``run_pass``). Returns
        ``"scale_up"`` / ``"scale_down"`` when an action fired, else
        None. An injected ``autoscale`` fault aborts the evaluation
        before ANY state changes (``stats["aborted"]``) — the fleet is
        left exactly as found."""
        now = self._now()
        if now < self._next_eval:
            return None
        self._next_eval = now + self.min_interval_s
        try:
            if _FAULTS.active:
                _FAULTS.fire("autoscale")
        except InjectedFault:
            self.stats["aborted"] += 1
            return None
        self.stats["evaluations"] += 1
        self._reap(router)
        sig = self.signals(router)
        self._refresh_gauge(router)
        n_live = int(sig["healthy"]) + len(
            [n for n in self._retiring if n in router.replicas])
        hot = (sig["queue"] >= self.queue_enter
               or sig["burn"] >= self.burn_enter
               or sig["free_slots"] <= self.headroom_enter_slots)
        calm = (sig["queue"] <= self.queue_exit
                and sig["burn"] <= self.burn_exit
                and sig["free_slots"] >= self.headroom_exit_slots)
        # explicit None checks: 0.0 is a legitimate virtual-clock
        # timestamp, not "never held"
        self._hot_since = (
            now if self._hot_since is None else self._hot_since
        ) if hot else None
        self._calm_since = (
            now if self._calm_since is None else self._calm_since
        ) if calm else None
        if now < self._cooldown_until:
            return None
        if (hot and n_live < self.max_replicas
                and now - self._hot_since >= self.min_hold_s):
            return self._scale_up(router, now, sig)
        if (calm and int(sig["healthy"]) > self.min_replicas
                and now - self._calm_since >= self.min_hold_s):
            return self._scale_down(router, now, sig)
        return None

    # -- scale-up ----------------------------------------------------------
    def _scale_up(self, router, now: float,
                  sig: Dict[str, float]) -> Optional[str]:
        from ..warmup import precompile
        made = self.replica_factory()
        registry = None
        if isinstance(made, tuple):
            if len(made) == 3:
                name, engine, registry = made
            else:
                name, engine = made
        else:
            name, engine = f"auto{self._spawn_counter}", made
        self._spawn_counter += 1
        # precompile-first gate: the replica walks its whole plan
        # against the shared persistent compilation cache BEFORE it can
        # take traffic; anything that would compile under load is
        # rejected here, where it costs nothing
        try:
            report = precompile(engine.adapter.app, registry=registry)
        except Exception:
            report = None
        if report is None or int(report.get("n_compiles", 1)) != 0:
            self.stats["rejected_cold"] += 1
            self._note(now, "reject_cold", name,
                       n_compiles=None if report is None
                       else report.get("n_compiles"))
            close = getattr(engine, "close", None)
            if close is not None:
                close()
            return None
        if router._registries is None:
            registry = None
        router.add_replica(name, engine, registry=registry)
        self._spawned.add(name)
        self.stats["scale_ups"] += 1
        self._cooldown_until = now + self.cooldown_s
        self._hot_since = None
        self._note(now, "scale_up", name,
                   n_compiles=int(report["n_compiles"]),
                   queue=round(sig["queue"], 3),
                   burn=round(sig["burn"], 3),
                   free_slots=sig["free_slots"])
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("fleet.scale_up", cat="fleet", replica=name,
                        reason="pressure",
                        n_compiles=int(report["n_compiles"]),
                        queue=round(sig["queue"], 3),
                        burn=round(sig["burn"], 3),
                        free_slots=sig["free_slots"])
        self._refresh_gauge(router)
        return "scale_up"

    # -- scale-down --------------------------------------------------------
    def _scale_down(self, router, now: float,
                    sig: Dict[str, float]) -> Optional[str]:
        victim = self._pick_victim(router)
        if victim is None:
            return None
        migrated = router.drain(victim, mode="migrate")
        self._retiring[victim] = victim in self._spawned
        self.stats["scale_downs"] += 1
        self._cooldown_until = now + self.cooldown_s
        self._calm_since = None
        self._note(now, "scale_down", victim, migrated=migrated,
                   queue=round(sig["queue"], 3),
                   burn=round(sig["burn"], 3))
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("fleet.scale_down", cat="fleet", replica=victim,
                        reason="idle", migrated=migrated,
                        queue=round(sig["queue"], 3),
                        burn=round(sig["burn"], 3))
        self._refresh_gauge(router)
        return "scale_down"

    def _pick_victim(self, router) -> Optional[str]:
        """Least-loaded healthy replica, preferring ones this
        controller spawned (retire elastic capacity before seed
        capacity), never below ``min_replicas`` healthy."""
        ranked = []
        for name in sorted(router.replicas):
            rep = router.replicas[name]
            if rep.state != HEALTHY or name in self._retiring:
                continue
            load = getattr(rep.engine, "load", None) or (0, 0)
            ranked.append((name not in self._spawned, tuple(load), name))
        if len(ranked) <= self.min_replicas:
            return None
        return min(ranked)[2]

    # -- retirement reaper -------------------------------------------------
    def _reap(self, router) -> None:
        """Phase 2 of scale-down: remove retiring replicas once their
        migrated-away drain has fully quiesced (no fleet-bound
        requests, no engine work)."""
        for name in list(self._retiring):
            rep = router.replicas.get(name)
            if rep is None:
                self._retiring.pop(name)
                continue
            bound = any(req.replica == name and not req.stream.finished
                        for req in router._requests.values())
            if bound or (rep.state != DEAD
                         and getattr(rep.engine, "has_work", False)):
                continue
            spawned = self._retiring.pop(name)
            engine = rep.engine
            try:
                router.remove_replica(name)
            except Exception:
                self._retiring[name] = spawned
                continue
            if spawned and not getattr(engine, "closed", False):
                close = getattr(engine, "close", None)
                if close is not None:
                    close()
            self.stats["reaped"] += 1

    # -- telemetry ---------------------------------------------------------
    def _refresh_gauge(self, router) -> None:
        reg = get_registry()
        if not reg.enabled:
            return
        gauge = tmetrics.fleet_replicas_gauge(reg)
        counts: Dict[str, int] = {}
        for rep in router.replicas.values():
            counts[rep.state] = counts.get(rep.state, 0) + 1
        for state in ("healthy", "draining", "backing_off",
                      "probation", "dead"):
            gauge.set(counts.get(state, 0), state=state)

    def _note(self, now: float, action: str, replica: str,
              **extra: Any) -> None:
        entry: Dict[str, Any] = {"t": round(now, 4), "action": action,
                                 "replica": replica}
        entry.update(extra)
        self.history.append(entry)
        del self.history[:-4096]       # bounded, like the router's _done
