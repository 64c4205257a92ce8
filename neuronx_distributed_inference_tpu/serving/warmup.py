"""Cold-start truth: the fleet precompile plane and the HBM ledger.

:func:`precompile` promotes the observatory's AOT walk into a
server-start warmup that drives the application's OWN jit entry points
(``_run_ragged`` / ``_run_paged_loop`` / ``_run_spec_verify`` /
``_run_paged``; prefill/decode for the contiguous app) across the
UNIFIED ragged row ladder (``autobucketing.ragged_row_buckets``) — not
fresh wrappers, so the serving-path jit caches are actually warm when
the first request lands. Every first-seen graph is timed into
``nxdi_compile_seconds{kind,bucket}`` and classified through jax's
compilation-cache monitoring events: a real XLA build increments
``nxdi_jit_compiles_total``, a persistent-cache load (N replicas share
the one cache directory utils/compile_cache.py resolves) counts as
``nxdi_jit_cache_hits_total`` instead. That split is what makes the
ROADMAP item-5 pin ("a second replica compiles nothing") fall out of the
counters.

**The report names programs by ``(kind, bucket)``, and
``benchmark/run.py`` labels the programs of a trace by that pair**
(``<kind>.w<bucket>``): its calibration calls ``precompile(app,
widths=[w])`` per width inside a profiler session and expects every entry
of ``graphs`` to be a distinct compiled program that ran exactly once. So
no two entries of one report share a pair, the walk dispatches nothing
but the step programs, ``("paged", 1)`` is the full-batch decode step and
``("paged", w)`` for ``w > 1`` the ``r_min``-row program a prefill chunk
carrying one prompt runs (its full-batch twin is ``("paged_pack", w)``):
pinned by tests/test_prefill_rows.py.

After the walk the application enters **declared steady state**
(:meth:`~..models.application.CausalLMApplication.declare_steady_state`):
any later first-seen signature is a tracked incident — the
``nxdi_steady_state_recompiles_total`` counter, a ``compile.unexpected``
flight-recorder event, attribution onto the triggering request's trace
lane, and exposure in ``/v1/debug/state["warmup"]``.

:func:`memory_ledger` is the live per-replica HBM account: exact model
parameter bytes, the paged KV pool split by block state (used / free /
unwritten, reconciling bit-for-bit with
``PagedEngineAdapter.debug_state()``'s block accounting), host-RAM
spill-tier residency, a fragmentation ratio, and the admission-headroom
estimate the scheduler logs when it rejects. Served as
``GET /v1/debug/memory`` (serving/engine/frontend.py) and aggregated
with per-replica labels through the fleet router.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from ..modules import autobucketing
from ..telemetry import metrics as tmetrics
from ..telemetry.registry import NULL_REGISTRY
from ..telemetry.trace import get_recorder as _get_recorder
from .adapter import carry_step_ids

__all__ = ["precompile", "memory_ledger", "WARMUP_SCHEMA", "LEDGER_SCHEMA"]

WARMUP_SCHEMA = "nxdi-warmup-report-v1"
LEDGER_SCHEMA = "nxdi-memory-ledger-v1"


# ---------------------------------------------------------------------------
# compilation-cache monitor: the truth behind compile-vs-load
# ---------------------------------------------------------------------------
class _CompileCacheMonitor:
    """Process-wide listener over jax's compilation-cache monitoring
    events. ``/jax/compilation_cache/cache_hits`` fires when an
    executable was DESERIALIZED from the persistent cache (no XLA
    build); ``cache_misses`` fires when the compiler actually ran. The
    split lets :func:`precompile` count a second replica's walk as cache
    hits rather than misreporting every persistent-cache load as a
    fresh compile."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self._installed = False
        self._lock = threading.Lock()

    def install(self) -> bool:
        with self._lock:
            if self._installed:
                return True
            try:
                from jax import monitoring
                monitoring.register_event_listener(self._on_event)
            except Exception:
                return False
            self._installed = True
            return True

    def _on_event(self, event: str, *args, **kwargs) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def snapshot(self):
        return (self.hits, self.misses)


_MONITOR = _CompileCacheMonitor()


# ---------------------------------------------------------------------------
# the precompile plane
# ---------------------------------------------------------------------------
def _paged_plan(app, widths, bt_widths, chunk_tokens, spec_widths):
    """The warm plan of a paged application: the unified ragged row
    ladder across every block-table width bucket, the two-phase graph at
    T=1 (``paged``, the full-batch decode step) and at the ctx-bucket
    chunk widths on both rungs of ``app.prefill_row_buckets`` (``paged``
    is the ``r_min``-row program a chunk carrying one prompt runs,
    ``paged_pack`` the full-batch one a packed chunk and ``generate()``
    run) — what the default ``PagedEngineAdapter(app)``, and a ragged
    adapter shed back to two-phase, dispatches — the fused decode loop,
    and the speculative verify widths: the exact shape set the serving
    adapters dispatch (serving/ragged/path.py, serving/adapter.py). Beside
    the decode step goes the program that makes its ids on the device when
    the live set changed under the step in flight (``carry_ids``,
    :func:`~.adapter.carry_step_ids`): one a batch bucket, and one a pair of
    them where the ladder has more than one rung (``carry_ids_from<rows>``).

    A recurrent/hybrid stack (``app.state_slots``) warms only the programs
    it can run: ``paged.w1``, ``paged.w<b>`` and ``paged_pack.w<b>`` (no
    ragged, fused-loop or verify program exists for it), one ``(kind,
    bucket)`` pair each like everyone else's; so does a stack with a
    learned sparse selection (``DecoderSpec.sparse``), which refuses the
    same three (``model_base.SPARSE_UNSUPPORTED``)."""
    cfg = app.tpu_config
    # stacks whose only programs are the paged ones
    paged_only = bool(app.state_slots) or app.spec.sparse is not None
    b = cfg.batch_size
    if widths is None:
        widths = autobucketing.ragged_row_buckets(app.ctx_buckets,
                                                  chunk_tokens)
    if bt_widths is None:
        bt_widths = list(app._bt_buckets)
    chunk = max(cfg.decode_chunk_tokens, 1)
    # a LoRA-built model traces a SECOND graph per shape once any row
    # carries an adapter slot (the adapter_ids kwarg changes the jit
    # signature) — warm both so the first multi-LoRA batch after
    # declare_steady_state() is a cache hit, not a sentinel trip.
    # slot 0 is the pinned zero adapter, so the dummy call writes nothing.
    lora_kw = ({"adapter_ids": np.zeros((b,), np.int32)}
               if app.spec.lora is not None else None)
    plan: List[tuple] = []

    def paged_thunk(w, rows, tw, lora=False):
        kw = {"adapter_ids": np.zeros((rows,), np.int32)} if lora else {}
        app._run_paged(np.zeros((rows, w), np.int32),
                       np.zeros((rows, w), np.int32),
                       np.full((rows, w), -1, np.int32),
                       np.zeros((rows, tw), np.int32),
                       np.zeros((rows,), np.int32),
                       state_slots=app._dummy_state_slots(rows), **kw)

    for tw in bt_widths:
        bt = np.zeros((b, tw), np.int32)        # null block only: no writes

        def ragged_thunk(w, bt=bt, **kw):
            # dummy no-write ragged dispatch: every slot negative, widths
            # ones, nothing emitted (mirrors PagedCausalLMApplication.
            # warmup's dummy-call discipline)
            app._run_ragged(np.zeros((b, w), np.int32),
                            np.zeros((b, w), np.int32),
                            np.full((b, w), -1, np.int32), bt,
                            np.ones((b,), np.int32),
                            np.zeros((b,), np.int32), **kw)

        for w in sorted(widths):
            if not paged_only:
                plan.append(("ragged", w,
                             lambda w=w, bt=bt: ragged_thunk(w, bt)))
                if lora_kw is not None:
                    plan.append(("ragged_lora", w, lambda w=w, bt=bt:
                                 ragged_thunk(w, bt, **lora_kw)))
            if w == 1 or w in app.ctx_buckets:
                # ("paged", w): the decode step at the full batch (w == 1),
                # the r_min-row chunk program (w > 1); the full-batch chunk
                # program is a kind of its own, so that no two entries of
                # the report share a (kind, bucket) pair
                rungs = [b] if w == 1 else app.prefill_row_buckets
                for kind, rows in zip(("paged", "paged_pack"), rungs):
                    plan.append((kind, w, lambda w=w, rows=rows, tw=tw:
                                 paged_thunk(w, rows, tw)))
                    if lora_kw is not None:
                        plan.append((kind + "_lora", w,
                                     lambda w=w, rows=rows, tw=tw:
                                     paged_thunk(w, rows, tw, lora=True)))
        if paged_only:
            continue
        if chunk > 1:
            plan.append(("paged_loop", chunk, lambda bt=bt: app._run_paged_loop(
                np.zeros((b,), np.int32), np.zeros((b,), np.int32), bt,
                chunk)))
            if lora_kw is not None:
                plan.append(("paged_loop_lora", chunk,
                             lambda bt=bt: app._run_paged_loop(
                                 np.zeros((b,), np.int32),
                                 np.zeros((b,), np.int32), bt, chunk,
                                 **lora_kw)))
        for w in sorted(spec_widths or ()):
            plan.append(("spec_verify", w, lambda w=w, bt=bt: app._run_spec_verify(
                np.zeros((b, w), np.int32), np.zeros((b, w), np.int32),
                np.full((b, w), -1, np.int32), bt,
                np.ones((b,), np.int32))))
            if lora_kw is not None:
                plan.append(("spec_verify_lora", w,
                             lambda w=w, bt=bt: app._run_spec_verify(
                                 np.zeros((b, w), np.int32),
                                 np.zeros((b, w), np.int32),
                                 np.full((b, w), -1, np.int32), bt,
                                 np.ones((b,), np.int32), **lora_kw)))

    def carry_thunk(rows_before, rows):
        # every row takes row 0's token of an all-zero step: ids only. The
        # tokens are placed as a decode step hands its own on (committed,
        # replicated): jit keeps another executable for a host array
        carry_step_ids(app, jax.device_put(np.zeros((rows_before,), np.int32),
                                           app._decode_ids_sharding),
                       np.zeros((rows,), np.int32),
                       np.full((rows, 1), -1, np.int32))

    if 1 in widths:
        # the decode step's rows are the state slots where a stack has them
        rungs = [b] if app.state_slots else app.batch_buckets
        plan += [("carry_ids" if before == rows
                  else f"carry_ids_from{before}", rows,
                  lambda before=before, rows=rows: carry_thunk(before, rows))
                 for rows in rungs for before in rungs]
    return plan


def _cb_plan(app):
    """Contiguous-app fallback plan: every prefill ctx bucket plus the
    decode step / fused decode loop per batch bucket (the same grid
    ``warmup()`` runs, instrumented per graph)."""
    cfg = app.tpu_config
    b = cfg.ctx_batch_size
    chunk = max(cfg.decode_chunk_tokens, 1)
    plan: List[tuple] = []
    for s in app.ctx_buckets:
        plan.append(("prefill", s, lambda s=s: app._run_prefill(
            np.zeros((b, s), np.int32), np.ones((b,), np.int32))))
    warm_batches = sorted(set(app.batch_buckets)
                          | {cfg.tkg_batch_size or cfg.batch_size})
    for bb in warm_batches:
        if chunk > 1:
            plan.append(("decode_loop", chunk, lambda bb=bb: app._run_decode_loop(
                np.zeros((bb,), np.int32), np.ones((bb,), np.int32),
                chunk)))
        plan.append(("decode", 1, lambda bb=bb: app._run_decode(
            np.zeros((bb, 1), np.int32), np.ones((bb, 1), np.int32))))
    return plan


def precompile(app, *, registry=None, widths: Optional[Sequence[int]] = None,
               bt_widths: Optional[Sequence[int]] = None,
               chunk_tokens: Optional[int] = None,
               spec_widths: Sequence[int] = (),
               declare_steady: bool = True) -> Dict[str, Any]:
    """Server-start precompile: walk the serving graph ladder through the
    application's own jit entry points, time every first-seen graph into
    ``nxdi_compile_seconds{kind,bucket}``, and classify it (XLA build vs
    persistent-cache load vs warm in-memory hit) into the existing
    ``nxdi_jit_compiles_total`` / ``nxdi_jit_cache_hits_total`` counters.

    ``registry``: the replica's metrics registry (defaults to the app's
    resolved telemetry registry). ``widths`` / ``bt_widths`` override the
    default ladders (tests shrink them); ``chunk_tokens`` feeds the
    ragged-row-bucket cap exactly like the adapter's
    ``prefill_chunk_tokens``. ``spec_widths``: speculative verify widths
    (k+1 per attached proposer) to warm. With ``declare_steady`` the app
    enters declared steady state afterwards — any later compile is a
    tracked incident (see the module docstring).

    Returns the ``nxdi-warmup-report-v1`` dict (also stored on the app
    for ``/v1/debug/state["warmup"]``)."""
    if app.params is None:
        app.init_random_weights()
    if app.cache is None:
        app.init_cache()
    reg = registry if registry is not None else app.telemetry
    monitored = _MONITOR.install()
    if hasattr(app, "_run_ragged"):
        plan = _paged_plan(app, widths, bt_widths, chunk_tokens,
                           spec_widths)
    else:
        plan = _cb_plan(app)
    # the entry points' own _note_jit would double-count into the app's
    # registry while this walk does its classified accounting — silence
    # it for the walk (the _jit_seen signature tracking still runs)
    prev_override = app._telemetry_override
    app._telemetry_override = NULL_REGISTRY
    graphs: List[Dict[str, Any]] = []
    n_compiles = n_loads = n_warm = 0
    t_total0 = time.perf_counter()
    try:
        for kind, bucket, thunk in plan:
            n_seen = len(app._jit_seen)
            hits0, misses0 = _MONITOR.snapshot()
            t0 = time.perf_counter()
            thunk()
            dt = time.perf_counter() - t0
            first_seen = len(app._jit_seen) > n_seen
            hits1, misses1 = _MONITOR.snapshot()
            if not first_seen:
                outcome = "warm"
                n_warm += 1
            elif (monitored and hits1 > hits0 and misses1 == misses0):
                outcome = "cache_load"
                n_loads += 1
            else:
                outcome = "compile"
                n_compiles += 1
            if reg.enabled:
                if outcome == "compile":
                    tmetrics.jit_compiles_counter(reg).inc(
                        kind=kind, bucket=str(bucket))
                else:
                    tmetrics.jit_cache_hits_counter(reg).inc(kind=kind)
                if first_seen:
                    tmetrics.compile_seconds_gauge(reg).set(
                        dt, kind=kind, bucket=str(bucket))
            graphs.append({"kind": kind, "bucket": bucket,
                           "seconds": dt, "outcome": outcome})
    finally:
        app._telemetry_override = prev_override
    total = time.perf_counter() - t_total0
    report = {
        "schema": WARMUP_SCHEMA,
        "n_graphs": len(graphs),
        "n_compiles": n_compiles,
        "n_cache_loads": n_loads,
        "n_warm_hits": n_warm,
        "total_seconds": total,
        "cache_monitored": monitored,
        "graphs": graphs,
        # which attention path each traced graph took (ops/kernel_mode.py)
        "kernels": app.warmup_state()["kernels"],
    }
    app._warmup_report = report
    if declare_steady:
        app.declare_steady_state()
    rec = _get_recorder()
    if rec.enabled:
        rec.instant("compile", cat="app", kind="precompile",
                    bucket=str(len(graphs)),
                    sig=f"compiles={n_compiles} loads={n_loads} "
                        f"warm={n_warm} total_s={total:.3f}")
    return report


# ---------------------------------------------------------------------------
# the HBM ledger
# ---------------------------------------------------------------------------
def _tree_bytes(tree) -> int:
    import jax
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(tree)))


def memory_ledger(adapter, *, registry=None,
                  graph_report: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    """One live per-replica HBM account over a
    :class:`~.adapter.PagedEngineAdapter`: exact model parameter bytes,
    the KV pool split by block state (reconciling with
    ``adapter.debug_state()["blocks"]`` exactly), spill-tier residency,
    fragmentation, and the admission-headroom estimate. Sets the
    ``nxdi_hbm_*`` gauges when ``registry`` is live; attaches per-graph
    ``memory_analysis()`` peaks when an observatory ``graph_report``
    (nxdi-graph-report-v1) is supplied."""
    app = adapter.app
    mgr = getattr(app, "kv_mgr", None)
    if mgr is None:
        # contiguous-layout adapter: no block accounting to reconcile —
        # report the static split only
        return {"schema": LEDGER_SCHEMA,
                "model_bytes": _tree_bytes(app.params),
                "kv": {"pool_bytes": _tree_bytes(app.cache)},
                "spill": None,
                "headroom": admission_headroom(adapter)}
    spec = mgr.spec
    # the KV pool alone: a recurrent/hybrid stack keeps its per-sequence
    # state (``ssm.ssm_state_shapes`` of its kind) in the same dict,
    # accounted below
    pool_bytes = _tree_bytes({k: app.cache[k] for k in ("k", "v")})
    # the window layers' ring pool of a stack with one (no allocator: a
    # ring a batch slot), beside the blocks the global layers book
    window_bytes = _tree_bytes({k: app.cache[k] for k in ("k_w", "v_w")
                                if k in app.cache})
    # a learned sparse selection's index keys, on the same blocks
    index_bytes = _tree_bytes({k: app.cache[k] for k in ("k_idx",)
                               if k in app.cache})
    state_bytes = (_tree_bytes(app.cache) - pool_bytes - window_bytes
                   - index_bytes)
    block_bytes = pool_bytes // spec.num_blocks
    usable = spec.num_blocks - 1               # block 0 is the null block
    free = int(mgr.allocator.num_free)
    in_use = usable - free
    unwritten = len(adapter._unwritten)
    live_tokens = sum(int(st.position) for st in adapter.seqs.values())
    live_tokens += sum(int(cst.done)
                       for cst in getattr(adapter, "_chunks", {}).values())
    alloc_slots = in_use * spec.block_size
    frag = (1.0 - live_tokens / alloc_slots) if alloc_slots else 0.0
    frag = min(max(frag, 0.0), 1.0)
    tier = getattr(adapter, "_kv_tier", None)
    spilled_bytes = int(tier.nbytes) if tier is not None else 0
    ledger: Dict[str, Any] = {
        "schema": LEDGER_SCHEMA,
        "model_bytes": _tree_bytes(app.params),
        "kv": {
            "pool_bytes": pool_bytes,
            "block_bytes": block_bytes,
            "block_size": int(spec.block_size),
            "blocks": {"usable": usable, "free": free, "in_use": in_use,
                       "unwritten": unwritten},
            "bytes": {"used": in_use * block_bytes,
                      "free": free * block_bytes,
                      "unwritten": unwritten * block_bytes,
                      "spilled": spilled_bytes},
            "live_tokens": live_tokens,
            "fragmentation_ratio": frag,
        },
        "spill": (None if tier is None else
                  {"blocks": len(tier), "bytes": spilled_bytes,
                   "stats": dict(tier.stats)}),
        "headroom": admission_headroom(adapter),
    }
    if index_bytes:
        ledger["kv"]["index_pool_bytes"] = index_bytes
        ledger["kv"]["index_block_bytes"] = index_bytes // spec.num_blocks
    ring = app.window_ring_pages
    if ring:
        n_window = app.spec.num_window_layers
        held = adapter.window_pool_rows()[0]
        ledger["kv"]["window_pool_bytes"] = window_bytes
        ledger["kv"]["pages"] = {
            "global": in_use * spec.num_layers,
            "window": held * n_window,
            "window_ring": ring, "window_slots": int(app.state_slots),
            "window_allocated": ring * int(app.state_slots) * n_window}
    if app.state_slots and state_bytes:
        ledger["state"] = {
            "bytes": state_bytes, "slots": int(app.state_slots),
            "slot_bytes": state_bytes // int(app.state_slots),
            "live": len(adapter._state_slot)}
    if graph_report is not None:
        # static side from the compiled-graph observatory: per-graph
        # memory_analysis() peaks (weights + temps while that graph runs)
        ledger["graphs"] = {
            g["label"]: g.get("memory", {}).get("peak_bytes")
            for g in graph_report.get("graphs", [])}
    reg = registry
    if reg is not None and reg.enabled:
        tmetrics.hbm_model_bytes_gauge(reg).set(ledger["model_bytes"])
        kv_gauge = tmetrics.hbm_kv_bytes_gauge(reg)
        for state, nbytes in ledger["kv"]["bytes"].items():
            kv_gauge.set(nbytes, state=state)
        tmetrics.kv_fragmentation_ratio_gauge(reg).set(frag)
    return ledger


def admission_headroom(adapter) -> Dict[str, int]:
    """The scheduler's capacity-reject log line: free batch slots, free
    KV blocks, and the token headroom they represent."""
    out = {"free_slots": int(getattr(adapter, "free_capacity", 0))}
    mgr = getattr(getattr(adapter, "app", None), "kv_mgr", None)
    if mgr is not None:
        free = int(mgr.allocator.num_free)
        out["free_blocks"] = free
        out["headroom_tokens"] = free * int(mgr.spec.block_size)
    return out
