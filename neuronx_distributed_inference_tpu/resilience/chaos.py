"""Chaos campaign: every fault point, one mixed fleet workload, global
invariants asserted after heal.

The fault harness (:mod:`.faults`) gave every recovery path a
deterministic trigger, and PRs 2-13 pinned each one in isolation — but
no test ever drove a REALISTIC mixed fleet workload (chunked prefill +
decode + speculative verify + ragged unified dispatch + KV spill tier +
disaggregated handoff + replica failover, staggered) through a
randomized fault schedule. :class:`ChaosCampaign` is that driver:

  * one **golden** fault-free run of the workload records every
    stream's greedy tokens;
  * one **cell** per (fault point x schedule) re-runs the same seeded
    workload with that point armed — ``single`` (first traversal) and
    ``repeat`` (Nth traversal, multiple times) schedules sweep the
    "fails immediately" and "fails mid-flight, twice" shapes;
  * after the cell heals (engine retries, replica quarantine/probation,
    fleet requeue, preemption replay — whatever the armed point
    provokes), the **global invariants** are asserted:

      1. every stream is bit-identical to the golden (requeued /
         replayed streams included — the Preempted recompute contract
         makes greedy failover lossless),
      2. no stream is lost (same key set, every one finished),
      3. the block free pool is EXACT (each app back to its baseline
         count, no leaked tables),
      4. zero ``_unwritten`` leaks on surviving adapters,
      5. the armed point actually fired (an unreachable point is a red
         cell, not silent vacuous green).

The campaign is fully seeded (prompts AND the router's backoff jitter),
so a red cell reproduces.
tests/test_resilience_control.py runs a seeded random subset tier-1 and
red-verifies the harness on a doctored invariant (a deliberately leaked
block must fail the campaign).

This module imports the serving stack lazily (inside the workload), so
``resilience/`` stays importable without jax.
"""

from __future__ import annotations

import json
import random
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

from .errors import CapacityError, HandoffError, ServingError, StepFailure
from .faults import FAULT_POINTS, FAULTS

__all__ = ["CHAOS_SCHEMA", "ChaosCell", "ChaosCampaign", "default_cells"]

CHAOS_SCHEMA = "nxdi-chaos-v1"

#: ``slow_step`` must be armed with a delay — armed bare it raises an
#: untyped InjectedFault BEFORE the adapters' typed-wrapping try blocks
#: (its documented use is driving deadline expiry, not failure).
_SLOW_STEP_DELAY_S = 0.002


@dataclass(frozen=True)
class ChaosCell:
    """One campaign cell: ``point`` armed to trip on traversals
    ``nth .. nth+times-1`` while the whole workload runs."""
    point: str
    schedule: str                  # "single" | "repeat"
    nth: int
    times: int
    delay_s: Optional[float] = None


def default_cells(points: Optional[Sequence[str]] = None
                  ) -> List[ChaosCell]:
    """The full sweep matrix: every registered fault point, single-shot
    (first traversal) and repeated-Nth (second + third traversals)."""
    cells: List[ChaosCell] = []
    for point in (points if points is not None else FAULT_POINTS):
        delay = _SLOW_STEP_DELAY_S if point == "slow_step" else None
        cells.append(ChaosCell(point, "single", nth=1, times=1,
                               delay_s=delay))
        cells.append(ChaosCell(point, "repeat", nth=2, times=2,
                               delay_s=delay))
    return cells


def _retrying(fn: Callable[[], Any], attempts: int = 6):
    """Drive one workload operation through the documented heal paths:
    typed retry-safe failures (rolled-back admissions/steps, handoff
    sides with state unchanged, injected pool-dry CapacityErrors) are
    simply retried — exactly what a production caller does. Non-retry-
    safe failures and every other error propagate."""
    last: Optional[BaseException] = None
    for _ in range(attempts):
        try:
            return fn()
        except StepFailure as e:
            if not e.retry_safe:
                raise
            last = e
        except (HandoffError, CapacityError) as e:
            last = e
    raise last


class ChaosCampaign:
    """Seeded chaos driver over three same-weights paged applications.

    ``apps`` is a sequence of THREE ``PagedCausalLMApplication``s built
    from identical weights (replicas of one model — the fleet premise):
    the workload puts a ragged+speculative engine on the first (plus
    the KV spill tier and the handoff decode role), a pipelined engine
    on the second (plus the handoff prefill role) and a standalone
    speculative engine on the third, so every registered fault point is
    traversed by construction. ``cell_hook`` (test-only) runs after a
    cell's workload heals and before its invariants are checked — the
    red-verification seam (a hook that leaks a block must turn the
    campaign red)."""

    def __init__(self, apps, *, seed: int = 0, max_new: int = 4,
                 max_passes: int = 3000,
                 cell_hook: Optional[Callable[["ChaosCampaign", str],
                                              None]] = None):
        apps = list(apps)
        if len(apps) != 3:
            from .errors import ConfigurationError
            raise ConfigurationError(
                "ChaosCampaign needs exactly 3 same-weights paged apps "
                f"(got {len(apps)}) — ragged+spec, pipelined, spec roles")
        self.apps = apps
        self.seed = seed
        self.max_new = max_new
        self.max_passes = max_passes
        self.cell_hook = cell_hook
        self._golden: Optional[Dict[str, Any]] = None
        self._baseline: List[int] = []

    # -- public surface ----------------------------------------------------
    def sample_cells(self, k: int) -> List[ChaosCell]:
        """A seeded random subset of the full matrix — the tier-1 smoke
        shape (one seed, a few cells, <20s) vs the bench's full sweep."""
        rng = random.Random(self.seed)
        return rng.sample(default_cells(), k)

    def run(self, cells: Optional[Sequence[ChaosCell]] = None
            ) -> Dict[str, Any]:
        """Golden run + every cell; returns the ``nxdi-chaos-v1``
        report (``report["ok"]`` is the campaign verdict — the caller
        asserts it, the harness never raises on a red cell)."""
        cells = list(cells) if cells is not None else default_cells()
        self._baseline = [app.kv_mgr.allocator.num_free
                          for app in self.apps]
        t0 = time.perf_counter()
        golden = self._run_workload()
        self._golden = golden
        self._check_clean("golden")
        bad_golden = [k for k, v in golden.items()
                      if v["reason"] != "length"]
        rows = [self._run_cell(cell) for cell in cells]
        ok = not bad_golden and all(r["ok"] for r in rows)
        return {
            "schema": CHAOS_SCHEMA,
            "ok": ok,
            "seed": self.seed,
            "points": sorted({c.point for c in cells}),
            "golden": {
                "streams": len(golden),
                "tokens": sum(len(v["tokens"]) for v in golden.values()),
                "bad": bad_golden,
            },
            "cells": rows,
            "wall_s": round(time.perf_counter() - t0, 2),
        }

    # -- one cell ----------------------------------------------------------
    def _run_cell(self, cell: ChaosCell) -> Dict[str, Any]:
        row: Dict[str, Any] = {
            "point": cell.point, "schedule": cell.schedule,
            "nth": cell.nth, "times": cell.times,
        }
        error = None
        result: Dict[str, Any] = {}
        stats: Dict[str, Any] = {}
        try:
            with FAULTS.inject(cell.point, nth=cell.nth, times=cell.times,
                               delay_s=cell.delay_s) as fp:
                result = self._run_workload(stats)
            row["trips"] = fp.trips
            row["calls"] = fp.calls
        except Exception as e:          # a cell must never kill the sweep
            error = f"{type(e).__name__}: {e}"
            row["trips"] = row["calls"] = -1
        if self.cell_hook is not None:
            self.cell_hook(self, cell.point)
        golden = self._golden or {}
        missing = sorted(set(golden) - set(result))
        mismatched = sorted(
            k for k in golden if k in result
            and (result[k]["tokens"] != golden[k]["tokens"]
                 or result[k]["reason"] != golden[k]["reason"]))
        pool = [(app.kv_mgr.allocator.num_free, len(app.kv_mgr.tables))
                for app in self.apps]
        checks = {
            "fired": error is None and row["trips"] >= 1,
            "streams_bit_identical": error is None and not mismatched,
            "no_stream_lost": error is None and not missing,
            "free_pool_exact": all(
                free == base and tables == 0
                for (free, tables), base in zip(pool, self._baseline)),
            "no_unwritten_leak": stats.get("unwritten_leaked", -1) == 0,
        }
        row.update(
            ok=error is None and all(checks.values()),
            checks=checks,
            requeues=stats.get("requeues", 0),
            quarantines=stats.get("quarantines", 0),
            replica_failures=stats.get("replica_failures", 0),
            migrations=stats.get("migrations", 0),
            error=error,
            mismatched=mismatched, missing=missing,
        )
        return row

    def _check_clean(self, label: str) -> None:
        for app, base in zip(self.apps, self._baseline):
            if app.kv_mgr.tables or app.kv_mgr.allocator.num_free != base:
                raise ServingError(
                    f"chaos {label} run left device state behind "
                    f"(tables={sorted(app.kv_mgr.tables)}, "
                    f"free={app.kv_mgr.allocator.num_free}/{base}) — the "
                    "workload itself is broken; fix it before sweeping")

    # -- the mixed workload ------------------------------------------------
    def _prompt(self, rng: random.Random, n: int,
                lo: int = 1, hi: int = 500) -> List[int]:
        return [rng.randrange(lo, hi) for _ in range(n)]

    def _run_workload(self, stats: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
        """One seeded mixed run over the three apps. Returns
        ``{stream key: {"tokens", "reason"}}``; ``stats`` (optional
        out-param) collects heal/leak accounting for the cell row."""
        from ..serving import PagedEngineAdapter
        from ..serving.engine import ServingEngine
        from ..serving.fleet import (EngineRouter, FleetAutoscaler,
                                     HostKVSpillTier, admit_handoff,
                                     capture_handoff, handoff_from_json,
                                     handoff_to_json, migrate)
        if stats is None:
            stats = {}
        rng = random.Random(self.seed)
        app_a, app_b, app_c = self.apps
        bs = app_a.kv_mgr.spec.block_size
        max_new = self.max_new
        tier = HostKVSpillTier(max_blocks=64)
        results: Dict[str, Any] = {}

        def detach_hooks():
            for app in self.apps:
                alloc = app.kv_mgr.allocator
                if getattr(alloc, "on_evict", None) is not None:
                    alloc.on_evict = None

        # ---- phase 1: disaggregated prefill -> decode handoff ----------
        # (raw adapters, the process-boundary JSON wire form; every side
        # heals by plain retry — state unchanged on a typed failure)
        p_handoff = self._prompt(rng, 2 * bs + 1)
        prefill_ad = PagedEngineAdapter(app_b)
        first = _retrying(
            lambda: prefill_ad.add_requests([800], [p_handoff]))
        toks_h = [first[800]]
        record = _retrying(lambda: capture_handoff(prefill_ad, 800))
        wire = json.loads(json.dumps(handoff_to_json(record)))
        decode_ad = PagedEngineAdapter(app_a, kv_spill_tier=tier)
        try:
            admitted = _retrying(
                lambda: admit_handoff(decode_ad, handoff_from_json(wire),
                                      801))
            toks_h.append(admitted[801])
            for _ in range(max_new - 2):
                toks_h.append(
                    _retrying(lambda: decode_ad.step([801])[801]))
            decode_ad.release([801])
            results["handoff"] = {"tokens": toks_h, "reason": "length"}
            # ---- phase 1.5: force LRU eviction so the spill tier (and
            # the kv_spill point) actually fires; the cold admission is
            # aborted, so its never-written hashes are purged
            usable = app_a.kv_mgr.spec.num_blocks - 1
            cold = self._prompt(rng, usable * bs, lo=600, hi=5000)

            def evict():
                app_a.kv_mgr.begin_sequence(999, cold)
                app_a.kv_mgr.abort_sequence(999)

            _retrying(evict)
        finally:
            detach_hooks()

        # ---- phase 2: the staggered mixed fleet ------------------------
        # A: ragged unified dispatch + speculation + spill tier (verify +
        #    prefill rows in ONE dispatch, restores from the tier);
        # B: pipelined decode + chunked prefill (the only non-retry-safe
        #    fault point, pipeline_flush, lives here) — fed exclusively
        #    through the ROUTER so a replica death fails over instead of
        #    losing streams;
        # C: standalone speculative path (spec_verify dispatches).
        adapter_a = PagedEngineAdapter(app_a, ragged=True, speculation=2,
                                       kv_spill_tier=tier)
        adapter_b = PagedEngineAdapter(app_b,
                                       kv_spill_tier=HostKVSpillTier(
                                           max_blocks=64))
        adapter_c = PagedEngineAdapter(app_c, speculation=2)
        eng_a = ServingEngine(adapter_a, starvation_bound_s=1e9)
        eng_b = ServingEngine(adapter_b, starvation_bound_s=1e9)
        eng_c = ServingEngine(adapter_c, starvation_bound_s=1e9)
        # a pinned-size autoscaler (min == max == 3, so it can never
        # act): every fleet pass still runs one closed-loop EVALUATION,
        # which is exactly the "autoscale" fault point — an injected
        # trip aborts the evaluation with the fleet unchanged, the
        # documented trivial heal
        autoscaler = FleetAutoscaler(lambda: None, min_replicas=3,
                                     max_replicas=3)
        router = EngineRouter(
            {"A": eng_a, "B": eng_b, "C": eng_c},
            backoff_base_s=0.005, backoff_max_s=0.05,
            quarantine_after=2, max_replica_failures=8, seed=self.seed,
            autoscaler=autoscaler)
        streams: Dict[str, Any] = {}
        try:
            prefix_b = self._prompt(rng, 2 * bs)
            # first wave: direct work on A (long prompt -> chunked rows
            # in the ragged grid) and C; the FIRST routed request lands
            # on idle B (least load) before any pass runs
            streams["a0"] = eng_a.submit(self._prompt(rng, 2 * bs + 1),
                                         max_new, tenant="tA")
            streams["c0"] = eng_c.submit(self._prompt(rng, bs + 1),
                                         max_new, tenant="tC")
            streams["r0"] = router.submit(
                prefix_b + self._prompt(rng, 2), max_new)
            self._drive(router, streams, passes=2)
            # staggered second wave: prefill chunks now share dispatches
            # with live decode/verify rows; r1 re-presents B's prefix so
            # warm-affinity routing keeps B loaded with pipelined decode
            streams["a1"] = eng_a.submit(self._prompt(rng, 2 * bs + 1),
                                         max_new, tenant="tA")
            streams["c1"] = eng_c.submit(self._prompt(rng, bs + 1),
                                         max_new, tenant="tC")
            streams["r1"] = router.submit(
                prefix_b + self._prompt(rng, 2), max_new)
            # ---- phase 2.5: live decode→decode migration of r1 -------
            # move the routed pipelined-decode stream B→A mid-decode and
            # then back A→B (two capture + two admit traversals, so the
            # repeated-Nth schedules of migrate_capture / migrate_admit
            # have a second call to trip on); each leg heals by plain
            # retry — an injected failure leaves BOTH engines unchanged
            rid_r1 = streams["r1"].request_id

            def migrate_r1(dst: str):
                req = router._requests.get(rid_r1)
                if (req is None or streams["r1"].finished
                        or req.replica == dst
                        or router.replicas[req.replica].state == "dead"
                        or router.replicas[dst].state != "healthy"):
                    return             # already failed over / finished:
                    # the stream is bit-identical either way, which is
                    # the invariant the cell checks
                migrate(router, rid_r1, dst=dst)

            for _ in range(self.max_passes):
                if streams["r1"].n_tokens >= 1 or streams["r1"].finished:
                    break
                self._drive(router, streams, passes=1)
            _retrying(lambda: migrate_r1("A"))
            self._drive(router, streams, passes=1)
            _retrying(lambda: migrate_r1("B"))
            self._drive(router, streams)
            stats["migrations"] = router.stats["migrations"]
            stats["unwritten_leaked"] = sum(
                len(ad._unwritten)
                for ad, eng in ((adapter_a, eng_a), (adapter_b, eng_b),
                                (adapter_c, eng_c))
                if not eng.closed)
            stats["requeues"] = router.stats["requeues"]
            stats["quarantines"] = router.stats["quarantines"]
            stats["replica_failures"] = router.stats["replica_failures"]
            for key, s in streams.items():
                results[key] = {"tokens": list(s.tokens),
                                "reason": s.finish_reason}
        finally:
            for eng in (eng_a, eng_b, eng_c):
                if not eng.closed:
                    eng.close()
            # recover dead replicas: a fatal teardown keeps its device
            # tables (the cache is donated away) — the operator rebuild
            # path reclaims them before the pool invariant is read
            for app in self.apps:
                for sid in list(app.kv_mgr.tables):
                    app.kv_mgr.end_sequence(sid)
            detach_hooks()

        # ---- phase 3: multi-LoRA adapter churn (app_a's pool) ----------
        # A bounded pool over MORE registered adapters than device slots:
        # one adapter-tagged ragged stream (the adapter_swap point fires
        # inside the transactional swap of its admission; a trip rolls
        # the admission back and plain retry heals it) followed by an
        # acquire/release churn that forces >= 3 evictions, so the
        # best-effort adapter_spill point is traversed repeatedly (a
        # trip is swallowed — the later re-acquire cold-loads instead of
        # restoring, bit-identical either way).
        if getattr(app_a.spec, "lora", None) is not None:
            import numpy as np

            from ..serving import LoraAdapterPool
            pool = LoraAdapterPool(app_a, host_cache_adapters=2)
            lw = app_a.params["layers"]
            nprng = np.random.default_rng(self.seed + 31)

            def adapter_arrays():
                arrs = {}
                for mod in app_a.spec.lora.target_modules:
                    sa = lw[f"lora_A_{mod}"].shape   # (L, slots, in, r)
                    sb = lw[f"lora_B_{mod}"].shape   # (L, slots, r, out)
                    arrs[mod] = (
                        (nprng.standard_normal((sa[0], sa[2], sa[3]))
                         * 0.05).astype(np.float32),
                        (nprng.standard_normal((sb[0], sb[2], sb[3]))
                         * 0.05).astype(np.float32))
                return arrs

            for i in range(pool.n_slots + 2):
                pool.register_arrays(f"l{i}", adapter_arrays())
            lora_ad = PagedEngineAdapter(app_a, ragged=True,
                                         lora_pool=pool)
            p_lora = self._prompt(rng, bs + 1)
            try:
                _retrying(lambda: lora_ad.add_requests(
                    [900], [p_lora], meta=[{"adapter": "l0"}]))
                toks_l: List[int] = []
                for _ in range(self.max_passes):
                    if len(toks_l) >= max_new:
                        break
                    out = _retrying(lambda: lora_ad.step([900]))
                    toks_l.extend(out.get(900, ()))
                lora_ad.release([900])
                results["lora"] = {"tokens": toks_l, "reason": "length"}
                names = [f"l{1 + i % (pool.n_slots + 1)}"
                         for i in range(2 * (pool.n_slots + 1))]
                for nm in names:
                    _retrying(lambda nm=nm: pool.acquire(nm))
                    pool.release(nm)
                stats["lora_swaps"] = pool.stats["swaps"]
                stats["lora_spills"] = pool.stats["spills"]
            finally:
                if 900 in app_a.kv_mgr.tables:
                    app_a.kv_mgr.end_sequence(900)
        return results

    def _drive(self, router, streams: Dict[str, Any],
               passes: Optional[int] = None) -> None:
        """Drive fleet passes until every stream finished (or ``passes``
        elapsed for the staggering pause), sleeping out replica backoff
        (``EngineRouter.backoff_wait_s``) when a pass makes no
        progress."""
        done = 0
        while passes is None or done < passes:
            if passes is None and all(s.finished
                                      for s in streams.values()) \
                    and not router.has_work:
                return
            delivered = router.run_pass()
            done += 1
            if passes is None and done >= self.max_passes:
                raise ServingError(
                    f"chaos workload wedged after {done} passes "
                    "(streams unfinished) — recovery did not converge")
            if not delivered:
                wait = router.backoff_wait_s()
                if wait:
                    time.sleep(wait)
