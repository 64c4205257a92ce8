"""Serving integration surface: ONE adapter, :class:`PagedEngineAdapter`,
over ONE application, ``PagedCausalLMApplication`` (block tables keyed by
seq_id; reference: the vLLM-facing surface of models/model_wrapper.py and
the slot_mapping / active_block_table contract of
block_kv_cache_manager.py).

The engine owns scheduling; the adapter owns device state:

  * ``add_requests(seq_ids, prompts)``  — admit prompts (blocks, chunk
    state, a state slot on a recurrent or window-pool stack) and prefill
    them in packed chunks (``defer=True``, the serving engine's call: admit
    only, the step calls run the chunks)
  * ``step(seq_ids=None)``              — one decode step for the given
    (default: all) running rows, dispatched and fetched: THIS step's tokens
  * ``step_ahead(seq_ids=None)``        — the same step with one step kept
    in flight: returns the PREVIOUS step's tokens (the serving engine's
    call)
  * ``step_many(k, seq_ids=None)``      — k fused decode steps in ONE
    device dispatch + ONE host fetch (the fused paged loop with in-graph
    KV-slot advance)
  * ``flush()``                         — retire the in-flight dispatch
    (no-op when none is)
  * ``release(seq_ids)``                — free rows, blocks, state slots

Decode pipeline (see README "Decode pipeline"):

  * ``step()`` means one thing: every call dispatches and synchronously
    fetches (``_step_eager``, the tests' reference).
  * ``step_ahead()`` enqueues step N+1, fed step N's sampled tokens ON THE
    DEVICE (the decode step hands them on as ``out["next_ids"]``, in the
    placement of its own ids input: one executable, no helper program
    between two steps), and only then blocks on step N. Scheduling, input
    preparation, token routing and the yield to the stream writers run
    while the device computes. ``ServingEngine`` calls it on the plain
    two-phase path (no speculation, no ragged dispatch,
    ``decode_steps_per_pass == 1``); token streams, counts and finish
    reasons are those of eager (tests/test_engine_lookahead.py,
    tests/test_decode_pipeline.py). ``pipeline_depth=0`` keeps
    ``step_ahead()`` eager too: the tests' and a debugger's way to take
    the lookahead out from under the engine. It is the option's only value
    beside the default ``None``.
  * A changed live set does NOT empty the pipeline. A finished or
    cancelled row (``release``), a preempted row and an admission that
    graduated a row do not block where they happen: the step stays in
    flight, and the next decode call, seeing that rows left and rows
    joined, CARRIES it: the new composition's ids are made on the device
    (``carry_step_ids``: a surviving row takes the in-flight step's sampled
    token from the row it had there, a joined row its host-known first
    token, pad rows follow row 0), the step is enqueued, and only then is
    the step before it fetched. A released row's in-flight token is dropped
    at the fetch; its KV growth goes with its blocks. What still drains
    (the step in flight is fetched synchronously and the new composition
    dispatched from host tokens): a preemption, a caller that steps another
    set of the running rows (backpressure), a seq_id re-admitted under a
    new state. ``host_stats`` counts ``overlapped_dispatches``,
    ``pipeline_carries_<admit|release>`` and
    ``pipeline_drains_<admit|release|preempt|liveset>``.
  * Deferred-failure contract: a device failure from step N surfaces at
    step N+1's fetch as a :class:`StepFailure` with ``retry_safe=False``;
    every in-flight lookahead step's host bookkeeping (positions, KV
    growth) is rolled back to the last DELIVERED token. The
    ``pipeline_flush`` fault point makes this deterministic in tests.
  * Hot-path host bookkeeping is incremental: per-(live set, batch bucket)
    scratch buffers are filled in place (``_PagedScratch``; ``_SlotScratch``
    where the step's rows are state slots), and the block-table array is
    refreshed only for rows whose block list actually grew.
  * The dispatch helpers (``_dispatch_*``) must never materialize device
    values — enforced by the ``host-sync`` pass of ``scripts/nxdi_lint.py``.

Chunked, packed, schedulable prefill (see README "Chunked prefill";
reference analog: "Ragged Paged Attention" arxiv 2604.15464):

  * each admitted prompt's uncached suffix is split into
    ``prefill_chunk_tokens``-sized chunks driven through the ``_run_paged``
    slot-mapping path (positions are arbitrary), so prompts up to
    ``seq_len`` are admissible regardless of the largest ctx bucket.
    Intermediate chunk samples are discarded; only the final chunk's token
    is delivered (tests/test_chunked_prefill.py).
  * chunks from DIFFERENT sequences pack as ragged rows of one dispatch of
    shape (rows, width): width the smallest ctx bucket covering the longest
    chunk packed, rows the smallest rung of the application's
    ``prefill_row_buckets`` (``[r_min, batch_size]``) covering the prompts
    packed; prompts that would fill less than half of the full batch go
    ``r_min`` at a time (the pack computes every row of the batch). Counted
    as ``nxdi_prefill_pad_waste``, ``nxdi_prefill_chunks_total`` and
    ``nxdi_bucket_selected_total{kind="prefill_rows"}``.
  * a prompt's chunks reach the device in one of THREE ways, one algorithm
    (``_advance_prefill``: so many chunk dispatches, back to back with no
    fetch between them, before a call's decode work) under three answers
    of :func:`chunks_before_step`:

      - a direct ``add_requests`` with default arguments BLOCKS: it runs
        the call's whole chain, fetches the last chunk's tokens and returns
        each prompt's first token (the tests', the scripts' and the fleet
        handoff's contract; a decoding row waits behind all of it);
      - under the serving engine with no budget the chunks are PACED: the
        engine admits with ``defer=True`` (``{}`` comes back) and each
        ``step_ahead()`` / ``step()`` runs ``k`` dispatches before its
        decode step. ``k = max(1, ceil(3 f))``, ``f`` the prefill
        dispatches a decode gap over the last ``_PACE_WINDOW`` (96) decode
        gaps, read from the counts ``_note_gap`` keeps anyway (no clock: the
        same request sequence paces alike on the CPU and on the chip), so
        that about one decode gap in three at most waits behind prefill and
        a light mix waits behind ONE chunk. Under ``step_ahead()`` a chain
        that finds a step in flight LEADS with one chunk, fetches that step
        and enqueues none; the next call, with nothing to fetch, issues the
        other ``k - 1`` and then the step (it runs behind the chunks
        whenever it is enqueued, and the first chunk covers the device
        meanwhile): the tokens in flight are not kept waiting behind the
        chain's host work, so a chain costs ONE long gap, not two. With no
        row decoding, or until the window has filled once, the whole chain
        runs at once and is fetched, as the blocking admission ran it, and
        (``step_ahead()``, the speculative step) its rows decode from that
        very call. A full-batch pack counts as one dispatch;
      - with ``prefill_budget_tokens`` set, ``k`` is pinned to ONE dispatch
        of at most that many prompt tokens, whatever the load (a direct
        ``add_requests`` defers too and returns ``{}``).

    A deferred prompt's first token comes from the call whose dispatch
    completes it. Under ``step_ahead()`` with a decode row live the last
    chunk of a paced or budgeted prompt is not waited for either: its rows are parked (``_Parked``; they
    stay in ``pending_prefill_ids``) and graduate one call later, behind
    the fetch of a decode step that was enqueued after the chunk, where
    their token is host-visible for nothing. Between two decode dispatches
    the host then blocks on nothing but the fetch of the step before.
    ``step()``, ``step_many()``, ``flush()`` and a call with no decode row
    to hide behind fetch at once. What the other step paths do with a
    deferred default admission: ``step_many()`` (the engine's
    ``decode_steps_per_pass > 1``) and the speculative step run the whole
    pending chain before their horizon, where the blocking admission ran
    it before them (they keep no decode gaps, so their window never fills;
    under a budget, one capped dispatch a horizon as before);
    ``ragged=True`` always deferred and packs chunk rows WITH decode rows.
    ``host_stats`` counts ``prefill_paced_passes`` / ``prefill_paced_chunks``
    (calls that ran ``k`` dispatches, and those dispatches),
    ``prefill_chains_whole`` and the rule's last reading
    ``prefill_pace_k`` / ``prefill_pace_f``.
  * half-prefilled sequences stay inside the resilience contracts: a chunk
    dispatch failure (``prefill_chunk`` fault point) rolls every sequence
    packed in that dispatch back via ``abort_sequence`` (never-fully-
    written blocks cannot poison the prefix cache), deadlines expire
    pending admissions BEFORE device work, and preemption may evict a
    pending sequence (its ``Preempted.tokens`` is the bare prompt).

Ragged unified dispatch (see README "Ragged dispatch"; serving/ragged/):
``ragged=True`` routes EVERY engine step — decode rows, speculative verify
windows, pending prefill chunks — through ONE
``model_base.paged_ragged_step`` dispatch planned by the
``RaggedBatchPlanner``. Admission always defers and
``prefill_budget_tokens`` becomes a per-step cap on packed prompt tokens.
Token streams stay bit-identical to the two-phase path, with and without
``speculation=`` (tests/test_ragged_dispatch.py).

Resilience contract (see README "Serving resilience"):

  * every boundary failure is typed (``resilience.errors``) — never a bare
    ``ValueError``/``RuntimeError`` (the ``error-paths`` lint pass);
  * ``add_requests`` is **transactional**: it either admits every sequence
    or rolls back all allocations/adapter state from the call;
  * the adapter **preempts** the lowest-priority running sequence when the
    block pool runs dry (``preemption_policy``), handing back
    :class:`Preempted` records via :meth:`PagedEngineAdapter.take_preempted`;
  * per-request wall-clock deadlines (``deadline_s``) and a
    decode-past-``seq_len`` guard bound each request's budget; both are
    horizon-aware (``step_many(k)`` checks them once for the whole k-step
    horizon, before any device work).
"""

from __future__ import annotations

import bisect
import collections
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..modules import autobucketing
from ..modules.block_kv_cache import slots_from_table_into
from ..ops import kernel_mode
from ..resilience.errors import (AdmissionError, CapacityError,
                                ConfigurationError, DeadlineExceeded,
                                SequenceStateError, ServingError, StepFailure)
from ..resilience.faults import FAULTS as _FAULTS
from ..resilience.preemption import (PREEMPTION_POLICIES, Preempted,
                                    pick_victim)
from ..telemetry import get_registry
from ..telemetry import metrics as tmetrics
from ..telemetry.request_trace import RequestTimeline
from ..telemetry.request_trace import trace_of as _trace_of
from ..telemetry.trace import get_recorder as _get_recorder


@dataclass
class _SeqState:
    position: int                 # position of last_token
    last_token: int
    running: bool = True
    tokens: List[int] = field(default_factory=list)  # prompt + generated
    prompt_len: int = 0
    admit_idx: int = 0            # adapter-wide admission counter (LIFO)
    deadline: Optional[float] = None   # absolute perf_counter() deadline
    expired_reported: bool = False     # deadline metric counted once
    meta: Any = None              # opaque engine passthrough (tenant, ...)


@dataclass
class _ChunkState:
    """Chunked-prefill progress for one PENDING admission (paged adapter):
    KV for ``prompt[:done]`` is written (or prefix-cached — ``done`` starts
    at the post-cut prefix-hit count); ``[done:]`` still has chunks to run.
    The sequence graduates to a :class:`_SeqState` when its final chunk's
    token materializes."""
    prompt: List[int]
    done: int                     # tokens whose KV is written/cached
    admit_idx: int
    t0: float                     # admission wall time (TTFT anchor)
    # the request's timeline (the engine's record, or add_requests' own for
    # a caller without one): ``dispatch`` and ``token`` are stamped here
    timeline: RequestTimeline
    deadline: Optional[float] = None
    expired_reported: bool = False
    meta: Any = None              # opaque engine passthrough (tenant, ...)


@dataclass
class _Inflight:
    """One dispatched-but-not-fetched decode step (``step_ahead()``).

    ``states`` pins the exact _SeqState objects the dispatch advanced:
    retire/rollback apply only where the identity still matches, so a row
    released (or preempted) and re-admitted under the same seq_id while
    the step was in flight can never receive the stale token."""
    live: Tuple[int, ...]
    states: Tuple[_SeqState, ...]
    b: int
    pad_to: int
    out: Dict[str, Any]
    t_dispatch: float
    grown: int = 0                # paged KV tokens grown for this dispatch
    rows: Optional[np.ndarray] = None   # output row of each live seq
    # the prefill dispatches enqueued before this step: once its tokens are
    # fetched, every one of them has run (:class:`_Parked`)
    chunks_before: int = 0


@dataclass
class _Parked:
    """One dispatched prefill chunk that holds prompts' LAST chunks and whose
    sampled tokens nobody waited for (the budgeted ``step_ahead()`` path).
    Its rows graduate where the tokens are host-visible for nothing: after
    the fetch of a decode step that was enqueued behind it. Until then they
    stay pending admissions (``_chunks``, ``done == len(prompt)``); the
    ``_ChunkState`` objects pin identity, so a row released, preempted or
    expired meanwhile (and its seq_id re-admitted) is passed over."""
    out: Dict[str, Any]
    no: int                       # host_stats["prefill_dispatches"] with it
    # (output row, seq_id, state) of each prompt that ends in this dispatch
    finals: List[Tuple[int, int, _ChunkState]]
    # (seq_id, state) of every sequence packed in it: the rollback set
    packed: List[Tuple[int, _ChunkState]]
    # (seq_id, state, tokens written) of every pending admission as of this
    # dispatch: what a fetch of it confirms written
    covered: List[Tuple[int, _ChunkState, int]]


def _meta_tenant(meta: Any) -> str:
    """Tenant label value from an opaque per-request ``meta`` payload: the
    serving engine passes mappings with a "tenant" key; everything else
    (including the non-engine default None) labels as ""."""
    try:
        return str(meta.get("tenant", ""))
    except AttributeError:
        return ""


def _meta_seed(meta: Any) -> int:
    """Per-request sampling seed from an opaque ``meta`` payload: the
    serving engine passes mappings with a "sampling_seed" key; everything
    else (including the non-engine default None) seeds as 0. Feeds the
    positionally coupled sampling stream (ops/sampling.stream_keys) —
    two requests with the same seed and prompt sample the same tokens."""
    try:
        return int(meta.get("sampling_seed", 0))
    except (AttributeError, TypeError, ValueError):
        return 0


def _meta_adapter(meta: Any) -> Optional[str]:
    """Named LoRA adapter from an opaque per-request ``meta`` payload: the
    serving engine passes mappings with an "adapter" key; everything else
    (including the non-engine default None, and empty/None values) means
    the base model. The adapter resolves the name against its attached
    :class:`~.lora_pool.LoraAdapterPool` at admission."""
    try:
        name = meta.get("adapter", None)
    except AttributeError:
        return None
    return str(name) if name else None


def _common_tenant(tenants) -> str:
    """The single tenant shared by every affected row, or "" when the set
    is empty or mixed — per-call failure counters label with ONE tenant,
    and a cross-tenant failure is attributed to none rather than to an
    arbitrary member."""
    ts = set(tenants)
    return ts.pop() if len(ts) == 1 else ""


def _trace_error(err):
    """Record ``err`` on the flight recorder (attaching ``err.trace_id``)
    when tracing is live; returns ``err`` so raise sites stay one-liners.
    Idempotent per exception — a re-wrapped error keeps its first event."""
    rec = _get_recorder()
    if rec.enabled and getattr(err, "trace_id", None) is None:
        rec.error(err)
    return err


# what drains an in-flight decode step (host_stats["pipeline_drains_<cause>"],
# nxdi_pipeline_drains_total{cause}): an admission graduated a row, a row
# was released, a row was preempted, or the caller stepped another set
_DRAIN_CAUSES = ("admit", "release", "preempt", "liveset")
# ... and what it is CARRIED across instead, its tokens merged into the next
# step's ids on the device (host_stats["pipeline_carries_<cause>"],
# nxdi_pipeline_carries_total{cause}): rows that joined, rows that left
_CARRY_CAUSES = ("admit", "release")

# the decode gaps the pacing rule looks back over (:func:`chunks_before_step`)
_PACE_WINDOW = 96


def chunks_before_step(gaps: int, dispatches: int, decoding: bool,
                       budgeted: bool,
                       window: int = _PACE_WINDOW) -> Optional[int]:
    """How many of the pending chunk dispatches a call runs before its
    decode step; ``None`` is the whole chain. A pure function of two counts
    the adapter keeps anyway (``_note_gap``): ``dispatches`` prefill
    dispatches were issued in the last ``gaps`` decode gaps, of which it
    remembers at most ``window``. No clock: the same request sequence paces
    alike on the CPU and on the chip.

      * ``budgeted`` (``prefill_budget_tokens`` set): one dispatch, capped
        by the caller, whatever the load;
      * nobody ``decoding``: the whole chain, since no row waits behind it;
      * the window has not filled once: the whole chain, what a blocking
        admission ran;
      * else ``max(1, ceil(3 f))`` with ``f = dispatches / gaps``: room for
        three times the observed prefill load, so that about one decode gap
        in three at most waits behind prefill, the share under which the
        median gap stays a bare step (PERF.md section 6, PR 63)."""
    if budgeted:
        return 1
    if not decoding or gaps < window:
        return None
    return max(1, -(-3 * dispatches // gaps))


def _async_fetch(x):
    """Start the device->host copy without blocking (no-op for array types
    without the API, e.g. plain numpy under test fakes)."""
    try:
        x.copy_to_host_async()
    except AttributeError:
        pass


def _merge_step_ids(prev_tokens, take, ids):
    """``ids`` where it holds a token (>= 0), else the previous step's
    sampled token of row ``take``."""
    carried = prev_tokens.reshape(-1)[take].astype(ids.dtype)[:, None]
    return jnp.where(ids < 0, carried, ids)


def carry_step_ids(app, prev_tokens, take, ids):
    """The ids of a decode step over ANOTHER live set than the step before
    it, made on the device: row ``r`` takes ``ids[r]`` where that is a token
    (a row that joined: its first token is host-known) and the previous
    step's sampled token of row ``take[r]`` where it is -1 (a row that
    stays). One small program a (rows before, rows after) pair, warmed with
    the step programs (serving/warmup.py) and noted like them, so a carry
    after ``declare_steady_state()`` builds nothing; its output is placed as
    the decode step places ``out["next_ids"]``, so the step it feeds is the
    one executable."""
    fn = app._compiled.get(("carry_ids", 0))
    if fn is None:
        rep = app._decode_ids_sharding
        # a callable of the app's own, as its step programs are: a second
        # replica in one process then loads it from the compile cache
        fn = app._compiled[("carry_ids", 0)] = jax.jit(
            functools.partial(_merge_step_ids),
            in_shardings=(rep, rep, rep), out_shardings=rep)
    app._note_jit("carry_ids", ids.shape[0], (prev_tokens.shape, ids.shape))
    return fn(prev_tokens, take, ids)


class _AdapterTelemetry:
    """Shared engine-adapter instrumentation: TTFT / per-step decode latency
    histograms, live-batch + pad-waste accounting, pipeline overlap/drains/
    steps-per-fetch, the gap between decode steps, one request span per
    seq_id. Host-side only (measures
    at the adapter boundary); every method is a cheap no-op while telemetry
    is disabled."""

    def __init__(self, engine: str, telemetry=None):
        self.engine = engine
        self._telemetry = telemetry
        self._requests: Dict[int, Dict[str, Any]] = {}

    @property
    def registry(self):
        return self._telemetry if self._telemetry is not None \
            else get_registry()

    def on_add(self, seq_ids: Sequence[int], prompts, t0: float,
               t_tokens: Sequence[float],
               live: int, padded: int, count_rows: bool = True,
               tenants: Optional[Sequence[str]] = None):
        """``t0``: the ``add_requests`` call's start. ``t_tokens``: where
        each request's first token of THIS admission was host-visible, the
        reading ``_fetch_prefill_tokens`` took (no clock is read here). On
        a first admission it is the timeline's ``token`` stamp; a request
        re-admitted after a preemption keeps that stamp and brings the
        instant of its recompute here."""
        reg = self.registry
        if not reg.enabled:
            return
        if tenants is None:
            tenants = [""] * len(seq_ids)
        hist = tmetrics.ttft_histogram(reg)
        for sid, prompt, tenant, t_token in zip(seq_ids, prompts, tenants,
                                                t_tokens):
            ttft = t_token - t0
            span = reg.start_span("request", engine=self.engine, seq_id=sid,
                                  tenant=tenant)
            span.t_start = t0
            span.event("first_token", at=t_token, ttft_s=ttft,
                       prompt_len=len(prompt))
            self._requests[sid] = {"span": span, "steps": 0,
                                   "t_token": t_token, "t_last": t_token,
                                   "tenant": tenant}
            hist.observe(ttft, engine=self.engine, tenant=tenant)
        tmetrics.requests_counter(reg).inc(len(seq_ids), engine=self.engine,
                                           event="added")
        tmetrics.generated_tokens_counter(reg).inc(live, engine=self.engine)
        if count_rows:
            # chunked admissions account their device rows per chunk
            # dispatch (on_prefill_chunk) instead
            self._rows(reg, "prefill", live, padded)

    def on_prefill_chunk(self, rows: int, padded_rows: int,
                         real_tokens: int, padded_tokens: int,
                         cross_decoder_tokens: int = 0):
        reg = self.registry
        if not reg.enabled:
            return
        tmetrics.prefill_chunks_counter(reg).inc(rows, engine=self.engine)
        if cross_decoder_tokens:
            tmetrics.prefill_tokens_cross_decoder_counter(reg).inc(
                cross_decoder_tokens, engine=self.engine)
        if padded_tokens:
            tmetrics.prefill_pad_waste_histogram(reg).observe(
                1.0 - real_tokens / padded_tokens, engine=self.engine)
        self._rows(reg, "prefill", rows, padded_rows)

    def on_prefill_dispatch(self, experts: str, attn: str):
        reg = self.registry
        if reg.enabled:
            tmetrics.prefill_dispatches_counter(reg).inc(
                engine=self.engine, experts=experts, attn=attn)

    def on_step(self, live_ids: Sequence[int], t0: float, padded: int,
                steps: int = 1):
        reg = self.registry
        if not reg.enabled:
            return
        now = time.perf_counter()
        n = len(live_ids)
        # per-STEP latency even for a fused k-step horizon, so the
        # histogram stays comparable across step()/step_many() modes
        tmetrics.decode_step_histogram(reg).observe((now - t0) / steps,
                                                    engine=self.engine)
        tmetrics.generated_tokens_counter(reg).inc(n * steps,
                                                   engine=self.engine)
        for sid in live_ids:
            info = self._requests.get(sid)
            if info is not None:
                info["steps"] += steps
                info["t_last"] = now
        self._rows(reg, "decode", n, padded, steps=steps)

    def on_spec_step(self, rows: Sequence[Tuple[int, int]], t0: float,
                     padded: int, width: int, drafted: int, accepted: int,
                     mode: str = "greedy"):
        """One speculative engine step: ``rows`` is (seq_id, tokens
        delivered) per live row — per-request TPOT counts every delivered
        token, and the spec counters pin the drafted/accepted split.
        ``mode`` labels the verify discipline (greedy | sampled) so the
        two acceptance regimes never alias in one series."""
        reg = self.registry
        now = time.perf_counter()
        delivered = 0
        for sid, n in rows:
            delivered += n
            info = self._requests.get(sid)
            if info is not None:
                info["steps"] += n
                info["t_last"] = now
        if not reg.enabled:
            return
        tmetrics.decode_step_histogram(reg).observe(now - t0,
                                                    engine=self.engine)
        tmetrics.generated_tokens_counter(reg).inc(delivered,
                                                   engine=self.engine)
        tmetrics.spec_drafted_counter(reg).inc(drafted, engine=self.engine,
                                               mode=mode)
        tmetrics.spec_accepted_counter(reg).inc(accepted,
                                                engine=self.engine,
                                                mode=mode)
        if drafted:
            tmetrics.spec_accept_rate_gauge(reg).set(accepted / drafted,
                                                     engine=self.engine,
                                                     mode=mode)
        tmetrics.spec_verify_width_histogram(reg).observe(
            width, engine=self.engine)
        self._rows(reg, "decode", len(rows), padded)

    def on_ragged_step(self, kind_rows: Dict[str, int], real_tokens: int,
                       padded_tokens: int):
        """One ragged unified dispatch: ``kind_rows`` maps row kind
        (decode/prefill/verify/pad) to rows packed; the pad-waste gauge
        tracks the last dispatch's (padded - real) / padded over the
        unified row-bucket grid."""
        reg = self.registry
        if not reg.enabled:
            return
        counter = tmetrics.ragged_rows_counter(reg)
        for kind, n in kind_rows.items():
            if n:
                counter.inc(n, engine=self.engine, kind=kind)
        if padded_tokens:
            tmetrics.ragged_pad_waste_gauge(reg).set(
                1.0 - real_tokens / padded_tokens, engine=self.engine)

    def on_overlap(self):
        reg = self.registry
        if reg.enabled:
            tmetrics.overlapped_dispatches_counter(reg).inc(
                engine=self.engine)

    def on_moe_tally(self, touched: int, slots: int, assigned: int,
                     read: int, picks: int, zero: int, rows: int,
                     rows_hit: int):
        reg = self.registry
        if reg.enabled:
            c = tmetrics.moe_group_rows_counter(reg)
            c.inc(rows_hit, engine=self.engine, hit="yes")
            c.inc(rows - rows_hit, engine=self.engine, hit="no")
            c = tmetrics.moe_experts_counter(reg)
            for count, n in (("touched", touched), ("slots", slots),
                             ("assigned", assigned), ("read", read)):
                c.inc(n, engine=self.engine, count=count)
            c = tmetrics.moe_assignments_counter(reg)
            for kind, n in (("held", assigned), ("zero", zero),
                            ("absent", picks - assigned - zero)):
                c.inc(n, engine=self.engine, kind=kind)

    def on_drain(self, cause: str):
        reg = self.registry
        if reg.enabled:
            tmetrics.pipeline_drains_counter(reg).inc(engine=self.engine,
                                                      cause=cause)

    def on_paced(self, chunks: int, whole: bool, k: int, f: float):
        reg = self.registry
        if reg.enabled:
            c = tmetrics.prefill_pacing_counter(reg)
            if whole:
                c.inc(engine=self.engine, count="whole_chains")
            else:
                c.inc(engine=self.engine, count="paced_passes")
                c.inc(chunks, engine=self.engine, count="paced_chunks")
            g = tmetrics.prefill_pace_gauge(reg)
            g.set(k, engine=self.engine, stat="k")
            g.set(f, engine=self.engine, stat="f")

    def on_carry(self, cause: str):
        reg = self.registry
        if reg.enabled:
            tmetrics.pipeline_carries_counter(reg).inc(engine=self.engine,
                                                       cause=cause)

    def on_fetch(self, steps: int):
        reg = self.registry
        if reg.enabled:
            tmetrics.steps_per_fetch_histogram(reg).observe(
                steps, engine=self.engine)

    def on_gap(self, seconds: float, behind: str):
        reg = self.registry
        if reg.enabled:
            tmetrics.decode_gap_histogram(reg).observe(
                seconds, engine=self.engine, behind=behind)

    def on_release(self, seq_ids: Sequence[int]):
        # pop unconditionally: requests admitted while telemetry was live
        # must not leak from _requests if it is disabled before release
        reg = self.registry
        released = 0
        for sid in seq_ids:
            info = self._requests.pop(sid, None)
            if info is None:
                continue
            released += 1
            span, steps = info["span"], info["steps"]
            span.event("released", decode_steps=steps)
            if reg.enabled and steps > 0:
                # first token -> LAST decode step, not -> release: a request
                # parked finished while the engine drains others must not
                # inflate its reported per-token latency
                tmetrics.tpot_histogram(reg).observe(
                    (info["t_last"] - info["t_token"]) / steps,
                    engine=self.engine, tenant=info.get("tenant", ""))
            span.end()
        if released and reg.enabled:
            tmetrics.requests_counter(reg).inc(released, engine=self.engine,
                                               event="released")

    def on_preempt(self, seq_id: int, reason: str, tenant: str = ""):
        # like on_release, the span is closed unconditionally so a request
        # preempted after telemetry is disabled cannot leak from _requests
        info = self._requests.pop(seq_id, None)
        if info is not None:
            info["span"].event("preempted", reason=reason)
            info["span"].end()
        reg = self.registry
        if reg.enabled:
            tmetrics.preemptions_counter(reg).inc(engine=self.engine,
                                                  reason=reason,
                                                  tenant=tenant)

    def on_deadline(self, seq_ids: Sequence[int],
                    tenants: Optional[Sequence[str]] = None):
        reg = self.registry
        if not seq_ids or not reg.enabled:
            return
        if tenants is None:
            tenants = [""] * len(seq_ids)
        counter = tmetrics.deadline_expired_counter(reg)
        for tenant in tenants:
            counter.inc(engine=self.engine, tenant=tenant)

    def on_step_failure(self, phase: str, tenant: str = ""):
        reg = self.registry
        if reg.enabled:
            tmetrics.step_failures_counter(reg).inc(engine=self.engine,
                                                    phase=phase,
                                                    tenant=tenant)

    def on_admission_rollback(self):
        reg = self.registry
        if reg.enabled:
            tmetrics.admission_rollbacks_counter(reg).inc(engine=self.engine)

    def _rows(self, reg, phase: str, live: int, padded: int,
              steps: int = 1):
        tmetrics.live_batch_gauge(reg).set(live, engine=self.engine)
        tmetrics.live_rows_counter(reg).inc(live * steps, engine=self.engine,
                                            phase=phase)
        if padded > live:
            tmetrics.pad_rows_counter(reg).inc((padded - live) * steps,
                                               engine=self.engine,
                                               phase=phase)


def _live_rows(seqs: Dict[int, _SeqState],
               seq_ids: Optional[Sequence[int]],
               pending=()) -> List[int]:
    """Running rows for a step call. ``pending`` holds seq_ids admitted but
    still mid-prefill (chunked admissions): they are known — not an error —
    but carry no decodable row yet, so they are skipped."""
    ids = sorted(seqs) if seq_ids is None else list(seq_ids)
    if seq_ids is not None:
        for sid in ids:
            if sid not in seqs and sid not in pending:
                raise SequenceStateError(f"seq_id {sid} is not running "
                                         "(released or never added)")
    return [sid for sid in ids if sid in seqs and seqs[sid].running]


def _validate_admission(seq_ids: Sequence[int],
                        prompts: Sequence[Sequence[int]], seq_len: int):
    """Reject malformed admissions BEFORE any state changes — an empty
    batch or a zero-length prompt must fail typed here, not as an opaque
    numpy ``max()`` crash three layers down."""
    if len(seq_ids) == 0:
        raise AdmissionError("add_requests called with empty seq_ids")
    if len(seq_ids) != len(prompts):
        raise AdmissionError("seq_ids and prompts length mismatch "
                             f"({len(seq_ids)} vs {len(prompts)})")
    if len(set(seq_ids)) != len(seq_ids):
        raise AdmissionError("duplicate seq_ids in one add_requests call")
    for sid, p in zip(seq_ids, prompts):
        if len(p) == 0:
            raise AdmissionError(f"zero-length prompt for seq_id {sid}")
        if len(p) > seq_len:
            raise AdmissionError(
                f"prompt for seq_id {sid} is {len(p)} tokens — beyond the "
                f"compiled seq_len {seq_len}")


def _resolve_deadlines(deadline_s, n: int,
                       t0: float) -> List[Optional[float]]:
    """Per-request absolute deadlines from a scalar (shared) or per-seq
    sequence of relative wall-clock budgets in seconds."""
    if deadline_s is None:
        return [None] * n
    if isinstance(deadline_s, (int, float)):
        return [t0 + float(deadline_s)] * n
    if len(deadline_s) != n:
        raise AdmissionError("deadline_s and seq_ids length mismatch")
    return [None if d is None else t0 + float(d) for d in deadline_s]


def _pre_step_checks(seqs: Dict[int, _SeqState], live: Sequence[int],
                     seq_len: Optional[int], telemetry: _AdapterTelemetry,
                     horizon: int = 1):
    """Per-request budget enforcement, BEFORE any device work or cache
    growth: wall-clock deadlines, then the decode-past-seq_len guard (a
    row at position seq_len-1 holds its last representable token — one
    more step would scatter KV out of bounds). ``horizon`` is the number
    of fused steps about to run (``step_many``); the guard covers the
    whole horizon. ``seq_len`` is None for rolling-window caches
    (slot = pos % window never overflows)."""
    now = time.perf_counter()
    expired = [s for s in live
               if seqs[s].deadline is not None and now >= seqs[s].deadline]
    if expired:
        fresh = [s for s in expired if not seqs[s].expired_reported]
        for s in fresh:
            seqs[s].expired_reported = True
        telemetry.on_deadline(fresh, [_meta_tenant(seqs[s].meta)
                                      for s in fresh])
        raise _trace_error(DeadlineExceeded(
            f"seq_ids {expired} exceeded their wall-clock deadline; "
            "release() them (or re-queue with a fresh budget) and step "
            "again", seq_ids=expired))
    if seq_len is None:
        return
    over = [s for s in live if seqs[s].position + horizon > seq_len]
    if over:
        raise _trace_error(CapacityError(
            f"decode step (horizon {horizon}) for seq_ids {over} would "
            f"write KV past the compiled seq_len {seq_len}; release them "
            "or rebuild with a larger seq_len", seq_ids=over))


def _repeat_row0(x: np.ndarray, pad_to: int) -> np.ndarray:
    """Pad a batch axis to ``pad_to`` by repeating row 0 — THE batch-pad
    invariant (pad rows recompute row 0's data and rewrite its cache
    slots with identical values; reference: vllm_cte_repadding,
    model_wrapper.py:1297-1313)."""
    return np.concatenate([x, np.repeat(x[:1], pad_to - x.shape[0],
                                        axis=0)])


def _pad_paged_rows(pad_to, ids, pos, slots, bt, last):
    """Repeat row 0 up to the batch bucket (see :func:`_repeat_row0`)."""
    b = ids.shape[0]
    if b == pad_to:
        return ids, pos, slots, bt, last
    return tuple(_repeat_row0(x, pad_to) for x in (ids, pos, slots, bt,
                                                   last))


# ---------------------------------------------------------------------------
# Per-composition scratch buffers (incremental host bookkeeping)
# ---------------------------------------------------------------------------

class _PagedScratch:
    """Reusable decode-step input buffers for one (live set, batch bucket,
    table-width bucket) composition on the paged adapter. The block-table
    array is refreshed incrementally (only rows whose block list grew);
    slot mappings are recomputed in place from the cached table.

    The mutable input buffers are DOUBLE-BUFFERED (ping-pong): jax's CPU
    backend may alias a suitably-aligned numpy array zero-copy, so
    refilling the buffer a still-in-flight lookahead dispatch aliases
    would corrupt its input mid-execution. Each fill() flips to the other
    (ids, pos, slots, bt, counts) set; a set is only rewritten after its
    dispatch was retired (one step in flight at most)."""

    def __init__(self, live: Sequence[int], pad_to: int, width: int,
                 block_size: int, seeds: Optional[Sequence[int]] = None,
                 aids: Optional[Sequence[int]] = None):
        b = len(live)
        self.live = tuple(live)
        self.b = b
        self.pad_to = pad_to
        self.width = width
        self.rows = None               # live[i]'s output is row i
        self.last = np.zeros((pad_to,), np.int32)    # immutable after init
        # per-sequence sampling-stream seeds are constants of the live
        # composition (request meta never changes mid-flight), so the
        # buffer is immutable after init like ``last`` — no ping-pong
        self.seeds = np.zeros((pad_to,), np.int32)   # immutable after init
        if seeds is not None:
            self.seeds[:b] = np.asarray(seeds, np.int32)
            if pad_to > b:
                self.seeds[b:] = self.seeds[0]
        # per-row LoRA adapter slots are constants of the live composition
        # too (a slot is pinned for the sequence's whole residency), so
        # the buffer is immutable after init like ``seeds``; None keeps
        # the no-adapter graphs byte-identical (the kwarg is never passed)
        self.aids = None
        if aids is not None:
            self.aids = np.zeros((pad_to,), np.int32)
            self.aids[:b] = np.asarray(aids, np.int32)
            if pad_to > b:
                self.aids[b:] = self.aids[0]
        self._bufs = [(np.empty((pad_to, 1), np.int32),
                       np.empty((pad_to, 1), np.int32),
                       np.empty((pad_to, 1), np.int32),
                       np.zeros((pad_to, width), np.int32),
                       [0] * b) for _ in range(2)]
        self._cur = 0
        self.ids, self.pos, self.slots, self.bt, self.counts = self._bufs[0]
        self.gather_idx = np.concatenate(
            [np.arange(b, dtype=np.intp),
             np.zeros(pad_to - b, dtype=np.intp)])
        self._block_size = block_size

    def fill(self, adapter, need_tokens: bool = True):
        self._cur ^= 1
        (self.ids, self.pos, self.slots, self.bt,
         self.counts) = self._bufs[self._cur]
        seqs = adapter.seqs
        mgr = adapter.app.kv_mgr
        for i, s in enumerate(self.live):
            st = seqs[s]
            self.pos[i, 0] = st.position
            if need_tokens:
                self.ids[i, 0] = st.last_token
        prev0 = self.counts[0]
        mgr.fill_block_table(self.bt[:self.b], self.live, self.counts)
        if self.pad_to > self.b:
            self.pos[self.b:] = self.pos[0, 0]
            if need_tokens:
                self.ids[self.b:] = self.ids[0, 0]
            if self.counts[0] != prev0:
                self.bt[self.b:] = self.bt[0]
        slots_from_table_into(self.slots, self.bt, self.pos,
                              self._block_size)


class _SlotScratch(_PagedScratch):
    """:class:`_PagedScratch` for a recurrent/hybrid stack: the decode
    step's rows ARE the state slots (row ``i`` is slot ``i``, always the
    full batch), so the step graph updates conv tails and recurrent state in
    place and gathers nothing. A slot that is not stepped — free, held by
    a pending prefill, or a running row left out of this call — is a DEAD
    row: slot mapping -1 (``run_layers_ssm`` leaves its tail and state as
    they were, and writes no KV), the null block table, a position that is
    not 0. ``rows`` maps ``live[i]`` to its output row."""

    def __init__(self, live: Sequence[int], slots: Sequence[int],
                 pad_to: int, width: int, block_size: int,
                 seeds: Optional[Sequence[int]] = None,
                 aids: Optional[Sequence[int]] = None):
        super().__init__(live, pad_to, width, block_size)
        self.rows = np.asarray(slots, np.intp)
        if seeds is not None:
            self.seeds[self.rows] = np.asarray(seeds, np.int32)
        if aids is not None:
            self.aids = np.zeros((pad_to,), np.int32)
            self.aids[self.rows] = np.asarray(aids, np.int32)
        # device feedback: the previous dispatch's tokens are already in
        # slot order, whatever its live set was (a row keeps its slot while
        # it lives: a step carried across a changed live set takes each
        # surviving slot's own token, PagedEngineAdapter._carried_ids)
        self.gather_idx = np.arange(pad_to, dtype=np.intp)
        for ids, pos, slots_, bt, _ in self._bufs:
            ids.fill(0)
            pos.fill(1)
            slots_.fill(-1)
            bt.fill(0)

    def fill(self, adapter, need_tokens: bool = True):
        self._cur ^= 1
        (self.ids, self.pos, self.slots, self.bt,
         self.counts) = self._bufs[self._cur]
        seqs = adapter.seqs
        rows = self.rows
        for i, s in enumerate(self.live):
            st = seqs[s]
            self.pos[rows[i], 0] = st.position
            if need_tokens:
                self.ids[rows[i], 0] = st.last_token
        live_bt = adapter.app.kv_mgr.block_table_array(self.live, self.width)
        self.bt[rows] = live_bt
        live_slots = np.empty((self.b, 1), np.int32)
        slots_from_table_into(live_slots, live_bt, self.pos[rows],
                              self._block_size)
        self.slots[rows] = live_slots


# ---------------------------------------------------------------------------
# The adapter
# ---------------------------------------------------------------------------

class PagedEngineAdapter:
    """vLLM-style engine adapter over the PAGED app: block tables keyed by
    seq_id, slot mappings computed from the tables (reference: the
    slot_mapping / active_block_table contract of
    block_kv_cache_manager.py + model_wrapper.py:1297-1313).

    ``preemption_policy`` ("lifo" | "fewest_generated" | None) arms
    recompute preemption: when the block pool cannot satisfy an allocation
    the lowest-priority running sequence is evicted, its blocks reclaimed,
    and a :class:`Preempted` record queued for :meth:`take_preempted` —
    the engine re-queues ``record.tokens`` as a fresh prompt. ``None``
    disables eviction (allocation failures then raise
    :class:`CapacityError` after rolling the call back). Pending chunked
    admissions are eligible victims too (``tokens`` = the bare prompt,
    ``n_generated == 0``).

    ``prefill_chunk_tokens`` bounds one sequence's per-dispatch prefill
    chunk (default: the largest ctx bucket — monolithic-equivalent, but
    prompts longer than that bucket are still admitted by walking them in
    bucket-sized chunks). ``prefill_budget_tokens`` defers prefill to the
    scheduler: ``add_requests`` returns ``{}`` and each ``step()`` runs at
    most one packed chunk dispatch of at most that many prompt tokens
    before its decode work (first tokens arrive from the completing
    ``step()``). Without it a direct ``add_requests`` blocks on the whole
    chain and the serving engine's deferred admissions are paced by the
    observed prefill load (the module docstring's three ways). Both are
    documented in README "Chunked prefill".

    ``pipeline_depth`` is ``None`` (``step_ahead()`` keeps one decode step
    in flight) or ``0`` (``step_ahead()`` is eager too: the tests'
    reference under the engine); ``step()`` is always eager. The decode
    path is the eager template (``_step_eager``), the lookahead
    (``_step_pipelined``: device-resident token feedback, deferred fetch,
    lookahead-aware rollback) and ``step_many``; see the module
    docstring."""

    engine_name = "paged"

    def __init__(self, app, telemetry=None,
                 preemption_policy: Optional[str] = "lifo",
                 pipeline_depth: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefill_budget_tokens: Optional[int] = None,
                 speculation=None, kv_spill_tier=None,
                 ragged: bool = False, lora_pool=None):
        cfg = app.tpu_config
        if not cfg.is_block_kv_layout:
            raise ConfigurationError("app must be built with "
                                     "is_block_kv_layout=True")
        if (preemption_policy is not None
                and preemption_policy not in PREEMPTION_POLICIES):
            raise ConfigurationError(
                f"unknown preemption_policy {preemption_policy!r}; expected "
                f"one of {PREEMPTION_POLICIES} or None")
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ConfigurationError("prefill_chunk_tokens must be >= 1")
        if prefill_budget_tokens is not None and prefill_budget_tokens < 1:
            raise ConfigurationError("prefill_budget_tokens must be >= 1")
        self.app = app
        self.batch = cfg.batch_size
        self.seqs: Dict[int, _SeqState] = {}
        self.telemetry = _AdapterTelemetry("paged", telemetry)
        self.preemption_policy = preemption_policy
        self.preempted: List[Preempted] = []
        self._admit_counter = 0
        self._pos_limit = (None if getattr(app.spec, "rolling_window", False)
                           else cfg.seq_len)
        # chunked prefill: width ladder clamped at the chunk bucket so
        # chunk dispatches only ever run already-compiled ctx-bucket shapes
        self._chunk_widths = autobucketing.prefill_chunk_buckets(
            app.ctx_buckets, prefill_chunk_tokens)
        self.prefill_chunk_tokens = (
            min(prefill_chunk_tokens, self._chunk_widths[-1])
            if prefill_chunk_tokens is not None else self._chunk_widths[-1])
        self.prefill_budget_tokens = prefill_budget_tokens
        self._chunks: Dict[int, _ChunkState] = {}   # pending admissions
        self._unwritten: set = set()   # allocated blocks not fully written
        if pipeline_depth not in (None, 0):
            raise ConfigurationError(
                f"pipeline_depth must be None (step_ahead() keeps one "
                f"decode step in flight) or 0 (step_ahead() is eager too), "
                f"got {pipeline_depth!r}; step() always returns its own "
                f"step's tokens: a caller that can take them one call late "
                f"calls step_ahead()")
        self.pipeline_depth = pipeline_depth
        self._inflight: Optional[_Inflight] = None
        # why the in-flight step can no longer be fed back (the live set
        # changed under it): named by whoever changed it, counted where
        # the step is drained
        self._drain_cause: Optional[str] = None
        self._drains = 0               # pipeline drains, every cause
        # the last point at which a decode step's tokens became
        # host-visible: (instant, the states live in it, prefill
        # dispatches and drains counted by then)
        self._gap_mark: Optional[tuple] = None
        # the prefill dispatches of each of the last decode gaps, and their
        # sum: what the pacing rule reads (chunks_before_step)
        self._pace_gaps: collections.deque = collections.deque(
            maxlen=_PACE_WINDOW)
        self._pace_sum = 0
        # chunk dispatches issued since the last decode step was enqueued:
        # what a paced chain has used of its k
        self._chain_issued = 0
        self._ready: Dict[int, int] = {}
        # final chunks dispatched under the budget and not waited for, in
        # dispatch order (:class:`_Parked`)
        self._parked: List[_Parked] = []
        self._scratch = None
        self._spec = None              # SpeculativeDecodePath
        self._ragged = None            # RaggedDispatchPath
        # degradation-controller actuators (resilience/controller.py):
        # shed flags are consulted per step, so flipping them mid-serve
        # changes DISPATCH SHAPE only — greedy token streams are
        # unaffected (pinned by tests/test_resilience_control.py)
        self._spec_shed = False        # clamp draft widths to 1 (no draft)
        self._ragged_shed = False      # ragged -> two-phase dispatching
        # plain-int host counters, always on: the benchmark's per-layer
        # metrics adapter.dispatches_per_token / .prefill_pad_share read them.
        # The decode counters (dispatches/blocking_fetches/...) count ONLY
        # decode work; chunked prefill keeps its own prefill_* set so the
        # two stay separately comparable.
        self.host_stats: Dict[str, Any] = {
            "dispatches": 0, "device_steps": 0,
            # decode dispatches whose program's engagement record says its
            # recurrent state was stepped by the state-step kernel
            # (kernel_mode.state_on_kernel)
            "dispatches_state_kernel": 0,
            "blocking_fetches": 0, "blocked_s": 0.0,
            # decode dispatches enqueued while the previous step was still
            # unfetched, in-flight steps drained synchronously because the
            # live set changed under them, by what changed it, and those
            # whose tokens fed the changed set's step on the device instead
            "overlapped_dispatches": 0,
            **{f"pipeline_drains_{c}": 0 for c in _DRAIN_CAUSES},
            **{f"pipeline_carries_{c}": 0 for c in _CARRY_CAUSES},
            # the intervals between two decode steps' tokens becoming
            # host-visible while a sequence was live at both (_note_gap):
            # all of them, those in which prefill dispatches were issued
            # (and how many), those of a second or more (and how many of
            # THOSE saw prefill: a cell's ramp-up is one or two, a hole
            # with nothing dispatched is none), the longest
            "decode_gaps": 0, "decode_gap_s": 0.0,
            "decode_gaps_behind_prefill": 0,
            "decode_gap_s_behind_prefill": 0.0,
            "prefill_dispatches_in_gaps": 0,
            "decode_gaps_over_1s": 0, "decode_gaps_over_1s_behind_prefill": 0,
            "decode_gap_max_s": 0.0,
            # prefill dispatches, and those whose program's engagement
            # record says its routed experts took the walk over the
            # touched experts (kernel_mode.experts_path)
            "prefill_dispatches": 0, "prefill_dispatches_moe_walk": 0,
            # ... and those whose program ran its attention on a prefill
            # kernel: over the latent pool (kernel_mode.
            # prefill_attn_on_kernel), over the K / V pools
            # (kernel_mode.paged_prefill_on_kernel)
            "prefill_dispatches_attn_kernel": 0,
            "prefill_dispatches_paged_attn_kernel": 0,
            "prefill_blocking_fetches": 0,
            "prefill_blocked_s": 0.0, "prefill_real_tokens": 0,
            "prefill_padded_tokens": 0,
            # deferred admissions with no budget (_advance_prefill): the
            # calls that ran k chunk dispatches before their decode step and
            # those dispatches, the calls that ran a whole chain (no row
            # decoding, or the window not full yet), and the rule's last
            # reading: k (0 = the whole chain) and f, the prefill dispatches
            # a decode gap over the window
            "prefill_paced_passes": 0, "prefill_paced_chunks": 0,
            "prefill_chains_whole": 0, "prefill_pace_k": 0,
            "prefill_pace_f": 0.0}
        # pad rows sample what row 0 samples (greedy, or the positionally
        # coupled stream with row 0's seed): a step's full-batch output is
        # then the next step's ids as it stands
        sc = cfg.on_device_sampling_config
        self._pads_follow_row0 = (sc is None or not sc.do_sample
                                  or sc.stream_seed is not None)
        # recurrent/hybrid stack (recognised from the spec, no knob): every
        # live sequence holds one STATE SLOT of the second cache beside the
        # KV pool (conv tails + the kind's state, ``app.state_slots`` = batch
        # rows), taken at admission inside the same transaction, freed at
        # release, preemption and rollback. Decode rows are laid out in
        # slot order (:class:`_SlotScratch`); what such a stack cannot do
        # is refused here, from the model code's one table.
        self._state_free: List[int] = list(range(app.state_slots))
        self._state_slot: Dict[int, int] = {}
        if app.state_slots:
            # a stack with a window pool holds a slot a live sequence for
            # the same reason (its ring of pages in the window layers'
            # pool) and refuses from its own table
            from ..models.model_base import (recurrent_refusal,
                                             window_pool_refusal)
            why = (window_pool_refusal if app.spec.ssm is None
                   else recurrent_refusal)([
                ragged and "ragged dispatch",
                speculation is not None and "speculation",
                kv_spill_tier is not None and "host KV spill / handoff"])
            if why:
                raise ConfigurationError(why)
            self.host_stats.update(state_slot_allocs=0, state_slot_frees=0,
                                   state_slots_live=0)
        # a decoder-hybrid-decoder (DecoderSpec.layer_kinds with layers that
        # read another layer's cache or scan output): the tokens of the
        # dispatched chunks that ran that second decoder, exact, and the
        # dispatches in which some row sampled (the others took the chunk
        # program's empty branch: model_base.second_decoder_tokens)
        kinds = app.spec.layer_kinds or ()
        self._cross_decoder = "cross" in kinds or "gmu" in kinds
        if self._cross_decoder:
            self.host_stats.update(prefill_tokens_cross_decoder=0,
                                   prefill_dispatches_sampled=0)
        # ... and the ``last_idx`` of a chunk row that samples nothing
        self._no_sample = -1 if self._cross_decoder else 0
        # a learned sparse selection (DecoderSpec.sparse): the index keys
        # ride the allocator's blocks, so admission, release, preemption
        # and prefix reuse need nothing new; what it does not run under is
        # refused from the model code's table, and every decode dispatch
        # counts what the rows selected of what they hold
        self._sparse = app.spec.sparse
        if self._sparse is not None:
            from ..models.model_base import sparse_refusal
            why = sparse_refusal([
                ragged and "ragged dispatch",
                speculation is not None and "speculation",
                kv_spill_tier is not None and "host KV spill / handoff"])
            if why:
                raise ConfigurationError(why)
            # ... and every dispatch, of either width, whether its program
            # scored and searched on the selection kernel
            # (kernel_mode.select_on_kernel)
            self.host_stats.update(
                sparse_tokens_selected=0, sparse_tokens_cached=0,
                kv_index_pages_held=0, sparse_dispatches=0,
                sparse_dispatches_select_kernel=0)
        # paged program shape -> its selection ran on the kernel
        # (_count_select)
        self._select_kernel_shapes: Dict[Tuple[int, int], bool] = {}
        # paged program shape -> its state is stepped by the kernel
        # (_state_on_kernel)
        self._state_kernel_shapes: Dict[Tuple[int, int], bool] = {}
        # the window layers' pool (DecoderSpec.window_pool): pages a slot's
        # ring has, counted at every decode dispatch against what the same
        # layers would hold at full length
        self._ring_pages = app.window_ring_pages
        if self._ring_pages:
            self.host_stats.update(kv_window_pages_held=0,
                                   kv_window_pages_unwindowed=0,
                                   kv_tokens_in_window=0,
                                   kv_tokens_running=0)
        # host-RAM KV spill tier (serving/fleet/kv_tier.py): evicted
        # prefix blocks spill their payloads host-side and re-admit via
        # async H2D restore instead of recompute-prefill (README "Fleet")
        self._kv_tier = kv_spill_tier
        self.host_stats["kv_spilled_blocks"] = 0
        self.host_stats["kv_restored_blocks"] = 0
        if kv_spill_tier is not None:
            app.kv_mgr.set_spill_hook(self._spill_block)
        # multi-LoRA adapter pool (serving/lora_pool.py, README "Multi-LoRA
        # serving"): per-request adapter names (meta "adapter" key) resolve
        # to pinned device slots at admission; every dispatch then carries
        # per-row adapter_ids so ONE step mixes rows from different
        # adapters (dispatches/step unchanged)
        if lora_pool is not None and lora_pool.app is not app:
            raise ConfigurationError(
                "lora_pool must be built over THIS adapter's application "
                "(its stacked slots back the per-row gather)")
        self._lora_pool = lora_pool
        self._lora_slots: Dict[int, int] = {}   # seq_id -> pinned slot
        self._lora_names: Dict[int, str] = {}
        self._adapter_shed = False
        if lora_pool is not None:
            self.host_stats["lora_rows"] = 0
            self.host_stats["lora_shed_requests"] = 0
        if speculation is not None:
            # deferred import: speculation/ imports this module
            from .speculation import SelfDraftProposer
            if isinstance(speculation, int):
                speculation = SelfDraftProposer(speculation)
        if ragged:
            # ragged unified dispatch (serving/ragged/, README "Ragged
            # dispatch"): ONE mixed prefill+decode+verify dispatch per
            # engine step; subsumes the prefill-budget serialization
            # point and composes with speculation=
            from .ragged import RaggedDispatchPath
            self._ragged = RaggedDispatchPath(self, speculation)
        elif speculation is not None:
            from .speculation import SpeculativeDecodePath
            self._spec = SpeculativeDecodePath(self, speculation)

    def add_requests(self, seq_ids: Sequence[int],
                     prompts: Sequence[Sequence[int]],
                     deadline_s: Union[None, float,
                                       Sequence[Optional[float]]] = None,
                     meta: Optional[Sequence[Any]] = None,
                     timelines: Optional[Sequence[RequestTimeline]] = None,
                     defer: bool = False) -> Dict[int, int]:
        """Transactional admission: either every sequence is admitted, or
        every ``begin_sequence`` allocation from this call is rolled back
        and cache state is exactly as before (pool pressure may still
        preempt RUNNING sequences first — that eviction is reported via
        :meth:`take_preempted` and survives a subsequent rollback, since
        the preempted work is handed back to the engine either way).

        Prefill is chunked + packed (see the module docstring): the
        uncached suffixes walk through ``prefill_chunk_tokens``-sized
        ragged rows of shared ctx-bucket dispatches, so any prompt up to
        ``seq_len`` is admissible. With ``prefill_budget_tokens`` set the
        device work is deferred entirely: this call returns ``{}`` and
        ``step()`` delivers each first token when its final chunk lands.
        ``defer=True`` is the serving engine's call (``_add_batch``): admit
        only, whatever the budget, and return ``{}``; the engine's step calls
        then run the chunks between decode steps (:meth:`_advance_prefill`).
        A caller that leaves it out keeps the blocking chain and its first
        tokens.

        ``meta`` (optional, one opaque object per sequence) is a scheduler
        passthrough: the adapter never interprets it beyond reading a
        "tenant" key for telemetry labels, and hands it back verbatim on
        :class:`Preempted` records so a requeue needs no side tables.

        ``timelines`` (optional, one per sequence) are the requests' own
        records of their way to a first token (telemetry/request_trace.py):
        the adapter stamps ``dispatch`` where a prompt's first chunk is
        enqueued and ``token`` where its first token is host-visible. A
        caller without them gets records of the adapter's own."""
        from ..modules.block_kv_cache import cut_cached_at_unwritten
        _validate_admission(seq_ids, prompts, self.app.tpu_config.seq_len)
        for sid in seq_ids:
            if sid in self.seqs or sid in self._chunks:
                raise AdmissionError(f"seq_id {sid} already running")
        live_now = len(self.seqs) + len(self._chunks)
        if live_now + len(seq_ids) > self.batch:
            # typed, BEFORE any state change — without this the chunked
            # packer would happily admit (it packs <= batch rows per
            # dispatch and loops) and the overflow would only surface as
            # an untyped bucket error on the next decode step
            raise AdmissionError(
                f"admitting {len(seq_ids)} sequences would put "
                f"{live_now + len(seq_ids)} live/pending rows on a "
                f"compiled batch of {self.batch}")
        t0 = time.perf_counter()
        deadlines = _resolve_deadlines(deadline_s, len(seq_ids), t0)
        if meta is not None and len(meta) != len(seq_ids):
            raise AdmissionError("meta and seq_ids length mismatch")
        metas = list(meta) if meta is not None else [None] * len(seq_ids)
        if timelines is None:
            timelines = [RequestTimeline() for _ in seq_ids]
        elif len(timelines) != len(seq_ids):
            raise AdmissionError("timelines and seq_ids length mismatch")
        app = self.app
        bs = app.kv_mgr.spec.block_size
        protect = frozenset(seq_ids)
        begun: List[int] = []
        try:
            for i, sid in enumerate(seq_ids):
                prompt = list(prompts[i])
                while True:
                    try:
                        blocks, c = app.kv_mgr.begin_sequence(sid, prompt)
                        begun.append(sid)
                        break
                    except CapacityError:
                        # never evict a sibling of this very call — the
                        # old monolithic path couldn't either (its seqs
                        # weren't running yet), and a same-call eviction
                        # would hollow out the return dict
                        victim = self._choose_victim(exclude=protect)
                        if victim is None:
                            raise
                        self._preempt(victim, reason="admission")
                # a hit on a block another pending/same-call sequence has
                # not fully written yet must be recomputed, not trusted
                n_hit = int(c) // bs
                c = cut_cached_at_unwritten(blocks, int(c), bs,
                                            self._unwritten)
                c = min(c, len(prompt) - 1)
                self._unwritten.update(blocks[n_hit:])
                if self._kv_tier is not None:
                    # swap instead of recompute: consecutive spilled
                    # full blocks past the device prefix hit restore by
                    # one batched H2D write; restored blocks stay in
                    # _unwritten until the call's first MATERIALIZED
                    # dispatch confirms the write chain, exactly like
                    # chunk-written blocks
                    c = self._restore_spilled(sid, prompt, blocks,
                                              int(c))
                self._take_state_slot(sid)
                self._admit_counter += 1
                self._chunks[sid] = _ChunkState(
                    prompt=prompt, done=int(c),
                    admit_idx=self._admit_counter, t0=t0,
                    timeline=timelines[i], deadline=deadlines[i],
                    meta=metas[i])
                self._bind_adapter(sid, metas[i])
        except ServingError:
            self._rollback_admission(begun)
            raise
        except Exception as e:
            self._rollback_admission(begun)
            self.telemetry.on_step_failure("prefill",
                                           _common_tenant(map(_meta_tenant,
                                                              metas)))
            raise _trace_error(StepFailure(
                "paged admission failed; all allocations from this call "
                "were rolled back", phase="prefill",
                seq_ids=seq_ids, retry_safe=True)) from e
        if (defer or self.prefill_budget_tokens is not None
                or self._ragged is not None):
            # deferred: step() drives the chunks (ragged mode always
            # defers — the unified dispatch packs chunk rows WITH decode
            # rows, so admission never serializes its own device work)
            return {}
        cache_before = app.cache
        token_at: Dict[int, float] = {}    # seq_id -> its first token's instant
        try:
            if _FAULTS.active:
                _FAULTS.fire("prefill_step")
            while any(s in self._chunks for s in seq_ids):
                self._prefill_step(only=protect, token_at=token_at)
        except ServingError:
            # transactional: a chunk failure mid-call rolls back the WHOLE
            # call — sequences already past their final chunk included
            self._rollback_admission(begun)
            raise
        except Exception as e:
            self._rollback_admission(begun)
            self.telemetry.on_step_failure("prefill",
                                           _common_tenant(map(_meta_tenant,
                                                              metas)))
            raise _trace_error(StepFailure(
                "paged prefill failed; all allocations from this call were "
                "rolled back", phase="prefill", seq_ids=seq_ids,
                retry_safe=app.cache is cache_before)) from e
        # telemetry only once the WHOLE call is past rollback — a sibling
        # chunk failure must not leave spans/counters for requests that
        # were never admitted
        self.telemetry.on_add(seq_ids, prompts, t0,
                              [token_at[s] for s in seq_ids],
                              live=len(seq_ids), padded=len(seq_ids),
                              count_rows=False,
                              tenants=[_meta_tenant(m) for m in metas])
        return {s: self._ready.pop(s) for s in seq_ids}

    def release(self, seq_ids: Sequence[int]):
        """Free rows and their blocks (and state slots). Never blocks: a
        decode step in flight for a released row stays in flight, its
        token for the row is dropped where it is fetched (:meth:`_retire`
        skips a state that is gone) and its KV growth goes with the row's
        blocks."""
        proposer = self._active_proposer
        if proposer is not None:
            proposer.forget(seq_ids)
        for sid in seq_ids:
            self._ready.pop(sid, None)
            self._lora_release(sid)
            if sid in self._chunks:
                # mid-prefill: blocks whose content never fully landed
                # must not survive as prefix-cache hits
                self._abort_prefill_rows([sid])
                continue
            if sid in self.seqs:
                self.seqs.pop(sid)
                self._scratch = None       # its blocks are gone; see add
                self._free_state_slot(sid)
                if sid in self.app.kv_mgr.tables:
                    self.app.kv_mgr.end_sequence(sid)
        self._note_stale("release")
        self.telemetry.on_release(seq_ids)

    def _tenant_of(self, seq_ids) -> str:
        """Common tenant label of ``seq_ids`` (running rows), "" when
        mixed/unknown — failure counters attribute per tenant only when
        the attribution is unambiguous."""
        return _common_tenant(_meta_tenant(self.seqs[s].meta)
                              for s in seq_ids if s in self.seqs)

    def _traces_of(self, seq_ids):
        """Request trace ids of ``seq_ids`` (running rows) — the
        attribution payload for steady-state recompile incidents
        (serving/warmup.py)."""
        return [_trace_of(self.seqs[s].meta)
                for s in seq_ids if s in self.seqs]

    # -- fetch helpers (the ONLY places that block on device output) -------
    def _fetch_rows(self, out, b: int, rows=None) -> np.ndarray:
        """The sampled tokens of the ``b`` live sequences: the first ``b``
        rows, or rows ``rows`` where the dispatch was not laid out live
        rows first (a slot-ordered decode step)."""
        t0 = time.perf_counter()
        with _get_recorder().span("fetch.tokens", cat="adapter",
                                  engine=self.engine_name, rows=b):
            toks = np.asarray(out["tokens"])
            tally = out.get("moe_tally")
            if tally is not None:
                # a decode step over expert layers: what its routing touched
                # and its expert path read, counted on the device (six
                # int32)
                self._count_moe_tally(np.asarray(tally))
        self.host_stats["blocking_fetches"] += 1
        self.host_stats["blocked_s"] += time.perf_counter() - t0
        toks = toks.reshape(toks.shape[0], -1)
        return toks[:b] if rows is None else toks[rows]

    def _count_moe_tally(self, tally: np.ndarray):
        """``[touched, assigned, read, picks, identity picks, group hits]``
        of one decode step (``moe.share_tally`` + ``moe.zero_tally`` +
        ``moe.group_tally`` summed over the expert layers) into
        ``host_stats``; the slots the first three
        are counted over are held experts x expert layers, once a step, and
        ``moe_experts_skipped`` is the slots the step did not read (a
        reader that sums and divides cannot subtract). ``moe_assignments``
        is every top-k pick of the live rows, ``moe_assignments_zero`` those
        that fell to identity experts (``MoESpec.zero_experts``),
        ``moe_rows_group_hit`` the live rows x expert layers whose chosen
        routing groups include one that holds an expert of this chip (every
        row, for a router without groups)."""
        spec = self.app.spec
        slots = spec.moe.num_held * spec.num_moe_layers
        touched, assigned, read, picks, zero, hit = (int(n) for n in tally)
        st = self.host_stats
        for key, n in (("moe_experts_touched", touched),
                       ("moe_assignments_held", assigned),
                       ("moe_experts_read", read),
                       ("moe_experts_skipped", slots - read),
                       ("moe_expert_slots", slots),
                       ("moe_assignments", picks),
                       ("moe_assignments_zero", zero),
                       ("moe_rows_group_hit", hit)):
            st[key] = st.get(key, 0) + n
        self.telemetry.on_moe_tally(touched, slots, assigned, read, picks,
                                    zero, picks // spec.moe.top_k, hit)

    def _note_gap(self, states: Sequence[_SeqState],
                  chunks_before: Optional[int] = None):
        """A decode step's tokens for ``states`` just became host-visible.
        If one of them was live at the previous such point too, the
        interval is a gap between tokens that a client saw: count it, by
        what it waited behind — ``prefill`` if prefill dispatches stood
        between the two steps ON THE DEVICE (how many: the chain's length),
        else ``drain`` if the in-flight step was drained, else ``none``.
        ``chunks_before`` is the count of prefill dispatches issued when
        this step was enqueued (``_Inflight.chunks_before``; default: by
        now, the eager step's): the chain is what was issued since the step
        before was enqueued, whenever either was fetched. Always on: one
        clock read and a few dict adds. A state that left never comes back
        (a replayed row is a new one), so no gap spans an empty live set."""
        now = time.perf_counter()
        st = self.host_stats
        mark = self._gap_mark
        if chunks_before is None:
            chunks_before = st["prefill_dispatches"]
        self._gap_mark = (now, states, chunks_before, self._drains)
        if mark is None or not states:
            return
        t_prev, before, prefills, drains = mark
        if not before or states[0] is not before[0]:
            # else: the live set as it was, the usual case
            was = {id(x) for x in before}
            if not any(id(x) in was for x in states):
                return
        gap = now - t_prev
        chain = chunks_before - prefills
        if len(self._pace_gaps) == self._pace_gaps.maxlen:
            self._pace_sum -= self._pace_gaps[0]
        self._pace_gaps.append(chain)
        self._pace_sum += chain
        st["decode_gaps"] += 1
        st["decode_gap_s"] += gap
        if chain:
            st["decode_gaps_behind_prefill"] += 1
            st["decode_gap_s_behind_prefill"] += gap
            st["prefill_dispatches_in_gaps"] += chain
        if gap >= 1.0:
            st["decode_gaps_over_1s"] += 1
            st["decode_gaps_over_1s_behind_prefill"] += bool(chain)
        if gap > st["decode_gap_max_s"]:
            st["decode_gap_max_s"] = gap
        self.telemetry.on_gap(gap, "prefill" if chain else
                              "drain" if self._drains > drains else "none")

    # -- public decode surface ---------------------------------------------
    def step(self, seq_ids: Optional[Sequence[int]] = None,
             token_room: Optional[Dict[int, int]] = None):
        """One decode step for ``seq_ids`` (default: every running row),
        dispatched and fetched: {seq_id: next token} for THIS step. Raises
        :class:`DeadlineExceeded` / :class:`CapacityError` before any
        device work when a row is over budget, and :class:`StepFailure`
        when the device step fails (KV growth rolled back, positions not
        advanced).

        With ``ragged=True`` every step — speculative or not — is ONE
        unified mixed dispatch through serving/ragged/ and returns
        {seq_id: [tokens]}. With ``speculation=`` attached the step is
        draft-and-verify and returns {seq_id: [tokens]} with 1..k+1
        tokens per row; ``token_room`` (scheduler hook) caps each row's
        tokens-delivered for this step.

        Degradation (resilience/controller.py): with the ragged path
        SHED the step falls back to two-phase dispatching — through the
        speculative path when a proposer is attached (its own shed flag
        composes), else the plain chunk-then-decode template, which
        already drives pending chunked admissions via
        ``_advance_prefill``. Greedy tokens are identical either way;
        only the dispatch count changes."""
        if self._ragged is not None:
            if not self._ragged_shed:
                return self._ragged.step(seq_ids, token_room)
            if self._ragged.spec_path is not None:
                return self._ragged.spec_path.step(seq_ids, token_room)
            return self._step_eager(seq_ids)   # 1 token/row: room is honored
        if self._spec is not None:
            return self._spec.step(seq_ids, token_room)
        if token_room is not None:
            raise ConfigurationError(
                "token_room is a speculative-decode hook; build the "
                "adapter with speculation= or ragged=True to use it")
        return self._step_eager(seq_ids)

    def step_ahead(self, seq_ids: Optional[Sequence[int]] = None):
        """:meth:`step` for a caller that can take a step's tokens one call
        late — the serving engine's loop. The ragged and speculative paths
        materialize every step and run as :meth:`step` does. On the plain
        two-phase path, unless the adapter was built with
        ``pipeline_depth=0``, the call enqueues this step BEFORE it blocks
        on the previous one and returns the previous step's tokens ({} on
        the first call after the pipeline empties), so the caller's own
        work between two calls (scheduling, routing, the yield to the
        stream writers) runs while the device computes. :meth:`flush`
        hands back what is still in flight (call it before going back to
        :meth:`step`); :attr:`lookahead_ids` names the rows that have such
        a token coming. A device failure of the step in flight surfaces at
        the NEXT call's fetch as a :class:`StepFailure` with
        ``retry_safe=False`` (:meth:`_abort_pipeline`)."""
        if self._ragged is not None or self._spec is not None:
            return self.step(seq_ids)
        if self.pipeline_depth == 0:
            return self._step_eager(seq_ids)
        return self._step_pipelined(seq_ids)

    @property
    def lookahead_ids(self) -> set:
        """seq_ids whose next token is sampled, or being sampled by the
        in-flight step, but not handed to the caller yet."""
        ids = set(self._ready)
        rec = self._inflight
        if rec is not None:
            ids.update(s for s, st in zip(rec.live, rec.states)
                       if self.seqs.get(s) is st)
        return ids

    def step_many(self, num_steps: int,
                  seq_ids: Optional[Sequence[int]] = None
                  ) -> Dict[int, List[int]]:
        """``num_steps`` fused decode steps in ONE device dispatch and ONE
        blocking host fetch. Returns {seq_id: [tokens]} in stream order;
        a token still in flight from :meth:`step_ahead` is drained first
        and prepended (it is simply the preceding token of the same
        stream). Deadlines and the seq_len guard are enforced once for the
        whole horizon, before any device work. EOS handling stays with the
        engine, at horizon boundaries.

        With ``ragged=True`` or ``speculation=`` attached, ``num_steps``
        becomes a per-row TOKEN budget: the path runs unified engine
        steps — each one materialized dispatch — until every row has
        delivered its budget (rows with high accept rates finish in fewer
        dispatches; no row ever overshoots)."""
        if num_steps < 1:
            raise ConfigurationError("step_many requires num_steps >= 1")
        if self._ragged is not None or self._spec is not None:
            out: Dict[int, List[int]] = {}
            remaining: Dict[int, int] = {}
            targets = seq_ids          # validated on the first pass only
            for _ in range(num_steps):
                live = _live_rows(self.seqs, targets, self._pending_ids())
                if seq_ids is not None:
                    # rows preempted mid-loop must not fail later passes
                    targets = [s for s in seq_ids
                               if s in self.seqs or s in self._chunks]
                ids = [s for s in live if remaining.get(s, num_steps) > 0]
                if not ids and not self._pending_ids():
                    break
                room = {s: remaining.get(s, num_steps) for s in ids}
                # route through step() so the degradation shed flags apply
                # here too (a shed plain step returns {seq_id: token})
                res = self.step(ids, token_room=room)
                if not res and not ids:
                    break          # pending-only pass made no tokens
                for s, toks in res.items():
                    toks = toks if isinstance(toks, list) else [toks]
                    out.setdefault(s, []).extend(toks)
                    remaining[s] = remaining.get(s, num_steps) - len(toks)
            return out
        if self._inflight is not None or self._ready or self._parked:
            self._stash_flush()
        # pending drained tokens stay in self._ready until this call is
        # past every fallible stage — a recoverable DeadlineExceeded /
        # CapacityError / StepFailure must not drop them from the stream
        pending = self._pending_ids()
        live = _live_rows(self.seqs, seq_ids, pending)
        if not live and not pending:
            return {s: [t] for s, t in self._drain_ready().items()}
        if _FAULTS.active:
            _FAULTS.fire("slow_step")
        if live:
            _pre_step_checks(self.seqs, live, self._pos_limit,
                             self.telemetry, horizon=num_steps)
        # under the budget at most ONE packed prefill-chunk dispatch per
        # horizon — the scheduler knob that keeps a long admission from
        # stalling decode; without it the whole pending chain, as the
        # blocking admission ran it
        self._advance_prefill(seq_ids)
        if not live:
            # (a row the chain just finished steps from the next call on,
            # whose horizon the caller sizes to the row's room)
            return {s: [t] for s, t in self._drain_ready().items()}
        t0 = time.perf_counter()
        live = self._grow_for_step(live, num_steps)
        if not live:
            return {s: [t] for s, t in self._drain_ready().items()}
        toks, pad_to = self._run_many(live, num_steps)
        res = {s: [t] for s, t in self._drain_ready().items()}
        for i, s in enumerate(live):
            st = self.seqs[s]
            st.position += num_steps
            row = [int(t) for t in toks[i]]
            for t in row:
                self._append_token(st, t)
            res.setdefault(s, []).extend(row)
        self.telemetry.on_step(live, t0, padded=pad_to, steps=num_steps)
        self.telemetry.on_fetch(num_steps)
        return res

    def flush(self) -> Dict[int, int]:
        """Retire the in-flight pipelined dispatch (if any), graduate every
        prompt whose last chunk was not waited for, and hand back every
        token not yet delivered: {seq_id: token}. {} in eager mode. A
        deferred fetch failure aborts the pipeline (StepFailure,
        ``retry_safe=False``)."""
        ready = self._drain_ready()
        rec, self._inflight = self._inflight, None
        try:
            if rec is not None:
                self._note_drain()
                ready.update(self._retire_or_abort([rec]))
            self._graduate()
        except BaseException:
            # the drained tokens were already generated and applied to
            # host state — keep them deliverable past the failure
            self._ready = {**ready, **self._ready}
            raise
        ready.update(self._drain_ready())
        return ready

    # -- eager path --------------------------------------------------------
    def _step_eager(self, seq_ids) -> Dict[int, int]:
        pending = self._pending_ids()
        live = _live_rows(self.seqs, seq_ids, pending)
        if not live and not pending:
            return self._drain_ready()
        if _FAULTS.active:
            _FAULTS.fire("slow_step")
        if live:
            _pre_step_checks(self.seqs, live, self._pos_limit,
                             self.telemetry)
        self._advance_prefill(seq_ids, decoding=bool(live))
        if not live:
            # (a row the chain just finished steps from the next call on:
            # this one returns ONE token a row, and owes it its first)
            return self._drain_ready()
        t0 = time.perf_counter()
        with _get_recorder().span("dispatch.build", cat="adapter"):
            live = self._grow_for_step(live)
            if not live:
                return self._drain_ready()
            scr = self._scratch_for(live)
            scr.fill(self)
        cache_before = self.app.cache
        try:
            if _FAULTS.active:
                _FAULTS.fire("decode_step")
            out = self._dispatch_decode(scr)
            self._chain_issued = 0
            new = self._fetch_rows(out, len(live), scr.rows)
        except ServingError:
            self._rollback_step_growth(live)
            self._scratch = None
            raise
        except Exception as e:
            self._rollback_step_growth(live)
            self._scratch = None
            self.telemetry.on_step_failure("decode", self._tenant_of(live))
            raise _trace_error(StepFailure(
                "paged decode step failed; KV growth was rolled back; "
                "positions were not advanced",
                phase="decode", seq_ids=tuple(live),
                retry_safe=self.app.cache is cache_before)) from e
        self._note_gap(tuple(self.seqs[s] for s in live))
        res = self._drain_ready()    # first tokens of finished prefills
        for i, s in enumerate(live):
            st = self.seqs[s]
            st.position += 1
            tok = int(new[i, 0])
            self._append_token(st, tok)
            res[s] = tok
        self.telemetry.on_step(live, t0, padded=scr.pad_to)
        self.telemetry.on_fetch(1)
        return res

    # -- pipelined path ----------------------------------------------------
    def _step_pipelined(self, seq_ids) -> Dict[int, int]:
        pending = self._pending_ids()
        live = _live_rows(self.seqs, seq_ids, pending)
        if not live and not pending:
            return self.flush()
        if _FAULTS.active:
            _FAULTS.fire("slow_step")
        if live:
            _pre_step_checks(self.seqs, live, self._pos_limit,
                             self.telemetry)
        # with a decode step to hide behind, a prompt's last chunk is not
        # waited for: its rows graduate behind a later step's fetch
        held = self._advance_prefill(seq_ids, park=bool(live),
                                     decoding=bool(live))
        live = self._rows_after_chain(live, seq_ids)
        if not live:
            return self.flush()
        ready = self._drain_ready()
        try:
            if held:
                # behind the chunk that leads a paced chain: the step in
                # flight is fetched, the next one waits for the next call
                prev, self._inflight = self._inflight, None
                ready.update(self._retire_or_abort([prev]))
            else:
                self._advance_pipeline(live, ready)
            ready.update(self._drain_ready())   # first tokens of graduates
            return ready
        except BaseException:
            # tokens drained (or retired) this call were already generated
            # and applied to host state — keep them deliverable past a
            # recoverable failure instead of dropping them from the stream
            self._ready = {**ready, **self._ready}
            raise

    def _advance_pipeline(self, live: List[int],
                          ready: Dict[int, int]) -> Dict[int, int]:
        prev, self._inflight = self._inflight, None
        matched = prev is not None and self._matches(prev, live)
        if prev is not None and not matched and not self._carries(prev, live):
            # another live set than rows that left and rows that joined can
            # make of the dispatch's: drain it synchronously
            self._note_drain()
            ready.update(self._retire_or_abort([prev]))
            prev = None
        t0 = time.perf_counter()
        with _get_recorder().span("dispatch.build", cat="adapter"):
            asked = len(live)
            try:
                live = self._grow_for_step(live)
            except ServingError:
                self._inflight = prev      # growth rolled itself back
                raise
            if not live:
                self._inflight = prev
                return ready
            if prev is not None and len(live) != asked:
                # preemption shrank the batch mid-call: drain the old
                # composition's dispatch before re-padding for the new one
                self._note_drain("preempt")
                ready.update(self._retire_or_abort([prev]))
                prev = None
            scr = self._scratch_for(live)
            # (growth that preempted nobody left ``live`` as it was asked)
            carried = prev is not None and not matched
            scr.fill(self, need_tokens=prev is None or carried)
            toks_dev = (None if prev is None
                        else self._carried_ids(prev, scr) if carried
                        else self._feedback_tokens(prev, scr))
        cache_before = self.app.cache
        try:
            if _FAULTS.active:
                _FAULTS.fire("decode_step")
            out = self._dispatch_decode(scr, toks_dev)
        except ServingError:
            self._rollback_step_growth(live)
            self._scratch = None
            self._inflight = prev          # lookahead step is still healthy
            raise
        except Exception as e:
            self._rollback_step_growth(live)
            self._scratch = None
            self._inflight = prev
            self.telemetry.on_step_failure("decode", self._tenant_of(live))
            raise _trace_error(StepFailure(
                "paged decode step failed at dispatch; KV growth was rolled "
                "back; the in-flight lookahead step was preserved",
                phase="decode", seq_ids=tuple(live),
                retry_safe=self.app.cache is cache_before)) from e
        rec = _Inflight(
            live=tuple(live),
            states=tuple(self.seqs[s] for s in live),
            b=len(live), pad_to=scr.pad_to, out=out, t_dispatch=t0,
            grown=1, rows=scr.rows,
            chunks_before=self.host_stats["prefill_dispatches"])
        for s in live:
            self.seqs[s].position += 1
        self._chain_issued = 0
        if carried:
            self._note_carry()
        self._drain_cause = None           # the step in flight is current
        # in flight before the fetch below: a row that graduates behind it
        # names itself as what changed this step's live set
        self._inflight = rec
        if prev is not None:
            self.host_stats["overlapped_dispatches"] += 1
            self.telemetry.on_overlap()
            ready.update(self._retire_or_abort([prev, rec]))
        return ready

    def _matches(self, rec: _Inflight, live: Sequence[int]) -> bool:
        return (rec.live == tuple(live)
                and all(self.seqs.get(s) is st
                        for s, st in zip(rec.live, rec.states)))

    def _carries(self, prev: _Inflight, live: Sequence[int]) -> bool:
        """Whether a step over ``live`` can take its ids from ``prev`` on
        the device: ``live`` is what rows that left (released, cancelled,
        expired) and rows that joined (graduated) make of ``prev``'s set.
        Every row ``prev`` stepped that is still running is stepped again,
        as the state it was then; a row new to ``prev`` holds its last token
        on the host. A caller that steps another set of running rows, a
        seq_id re-admitted under a new state and a preemption are drained
        as before."""
        if self._drain_cause not in _CARRY_CAUSES:
            return False
        was = dict(zip(prev.live, prev.states))
        stepped = set(live)
        return (all(s in stepped for s, st in was.items()
                    if self.seqs.get(s) is st)
                and all(was.get(s, self.seqs[s]) is self.seqs[s]
                        for s in live))

    def _carried_ids(self, prev: _Inflight, scr):
        """The ids of the step over ``scr``'s rows from ``prev``'s output
        ON THE DEVICE (:meth:`_carries` holds): a row that stays takes its
        sampled token from the row it had in ``prev`` (in a
        :class:`_SlotScratch` rows are slots and the index is the
        identity), a row that joined the token ``scr.fill`` wrote for it,
        and a pad row follows row 0 in both. No host round trip, one small
        program (:func:`carry_step_ids`)."""
        old_row = {s: i if prev.rows is None else int(prev.rows[i])
                   for i, s in enumerate(prev.live)}
        take = np.zeros((scr.pad_to,), np.int32)
        ids = scr.ids.copy()           # never the buffer a dispatch aliases
        for i, s in enumerate(scr.live):
            if s in old_row:
                r = i if scr.rows is None else scr.rows[i]
                take[r] = old_row[s]
                ids[r, 0] = -1
        if scr.rows is None and scr.pad_to > scr.b:
            take[scr.b:] = take[0]
            ids[scr.b:] = ids[0]
        return carry_step_ids(self.app, prev.out["tokens"], take, ids)

    def _note_carry(self):
        cause, self._drain_cause = self._drain_cause, None
        self.host_stats[f"pipeline_carries_{cause}"] += 1
        self.telemetry.on_carry(cause)

    def _note_stale(self, cause: str):
        """Rows left or joined (``cause``) while a step is in flight. The
        step stays in flight — nothing blocks here; the next decode call
        sees the changed live set and carries the step's tokens into the
        new set's step on the device (or, where it cannot, drains it),
        counted under ``cause``. If none of its rows is left, nobody is
        owed its tokens: it is dropped unfetched."""
        rec = self._inflight
        if rec is None:
            return
        if all(self.seqs.get(s) is not st
               for s, st in zip(rec.live, rec.states)):
            self._inflight = None
            self._drain_cause = None
        elif self._drain_cause is None:
            self._drain_cause = cause

    def _note_drain(self, default: str = "liveset"):
        cause, self._drain_cause = self._drain_cause or default, None
        self._drains += 1
        self.host_stats[f"pipeline_drains_{cause}"] += 1
        self.telemetry.on_drain(cause)

    def _feedback_tokens(self, prev: _Inflight, scr):
        """The previous dispatch's on-device sampled tokens as the next
        step's input ids — no host round trip. A paged decode step hands
        them on ready-made (``out["next_ids"]``: the live set is unchanged,
        so pad rows are clones of row 0 and a slot-ordered step has no pad
        rows): no program runs between two steps. Otherwise they are
        re-padded ON DEVICE (pad rows must stay clones of row 0 even under
        unseeded stochastic sampling)."""
        nxt = prev.out.get("next_ids")
        if nxt is not None and (scr.pad_to == scr.b or scr.rows is not None
                                or self._pads_follow_row0):
            return nxt
        toks = prev.out["tokens"].reshape(-1)
        if scr.pad_to > scr.b:
            toks = toks[scr.gather_idx]
        return toks[:, None]

    def _retire(self, rec: _Inflight) -> Dict[int, int]:
        """Materialize ``rec``'s tokens (the ONE blocking sync of the
        pipelined path) and apply the deferred host bookkeeping. Raises
        the raw fetch failure — callers route it through
        :meth:`_abort_pipeline`."""
        with _get_recorder().span("dispatch.retire", cat="adapter",
                                  engine=self.engine_name, rows=rec.b):
            if _FAULTS.active:
                _FAULTS.fire("pipeline_flush")
            new = self._fetch_rows(rec.out, rec.b, rec.rows)
            self._note_gap(rec.states, rec.chunks_before)
            res = {}
            for i, (s, st) in enumerate(zip(rec.live, rec.states)):
                if self.seqs.get(s) is not st:
                    continue           # released/preempted while in flight
                tok = int(new[i, 0])
                self._append_token(st, tok)
                res[s] = tok
            self.telemetry.on_step(list(res), rec.t_dispatch,
                                   padded=rec.pad_to)
            self.telemetry.on_fetch(1)
        return res

    def _retire_or_abort(self, records: List[Optional[_Inflight]]
                         ) -> Dict[int, int]:
        try:
            res = self._retire(records[0])
        except Exception as e:
            self._abort_pipeline(records, e)
        # the chunks enqueued before that step have run: their rows' first
        # tokens are host-visible for nothing
        try:
            self._graduate(records[0].chunks_before)
        except BaseException:
            # what was fetched is still owed; a step enqueued on top of the
            # failed chunk is unwound like one on top of a failed step
            self._ready = {**res, **self._ready}
            self._unwind_inflight(records[1:])
            raise
        return res

    def _abort_pipeline(self, records: Sequence[Optional[_Inflight]],
                        cause: Exception):
        """A deferred fetch failed: the in-flight step's device output (and
        any dispatch speculatively issued on top of it) is garbage. Unwind
        every in-flight dispatch's host bookkeeping — positions and paged
        KV growth return to the last DELIVERED token — and raise a
        :class:`StepFailure` with ``retry_safe=False`` (the donated device
        cache was consumed by the failed dispatch chain; re-admit or
        rebuild). A final chunk nobody waited for is part of that chain:
        every sequence packed in one is rolled back as a failed chunk
        dispatch rolls it back."""
        parked, self._parked = self._parked, []
        for p in parked:
            self._abort_prefill_rows(self._still_pending(p.packed))
        self._unwind_inflight(records)
        seq_ids = next((rec.live for rec in records if rec is not None), ())
        self.telemetry.on_step_failure("decode", self._tenant_of(seq_ids))
        raise _trace_error(StepFailure(
            "pipelined decode fetch failed; every in-flight lookahead step "
            "was rolled back to the last delivered token",
            phase="decode", seq_ids=seq_ids, retry_safe=False)) from cause

    def _unwind_inflight(self, records: Sequence[Optional[_Inflight]]):
        """Nothing is in flight any more: the positions and the paged KV
        growth of ``records`` return to the last token fetched."""
        self._scratch = None
        self._inflight = None
        self._drain_cause = None
        for rec in records:
            if rec is None:
                continue
            for s, st in zip(rec.live, rec.states):
                if self.seqs.get(s) is st:
                    st.position -= 1
            self._unwind_inflight_growth(rec)

    def _unwind_inflight_growth(self, rec: _Inflight):
        if not rec.grown:
            return
        for s, st in zip(rec.live, rec.states):
            if self.seqs.get(s) is st and s in self.app.kv_mgr.tables:
                self.app.kv_mgr.shrink(s, rec.grown)

    def _drain_ready(self) -> Dict[int, int]:
        if not self._ready:
            return {}
        out, self._ready = self._ready, {}
        return out

    def _stash_flush(self):
        """flush() into the pending buffer, so tokens drained by
        add/release/step_many are handed back by the next returning call
        instead of being dropped."""
        for s, t in self.flush().items():
            self._ready[s] = t

    # -- decode dispatch ---------------------------------------------------
    @property
    def _active_proposer(self):
        """The draft proposer of whichever decode path is engaged (the
        standalone speculative path OR the ragged unified path), None
        without speculation — release/preemption must drop per-sequence
        proposer state through exactly one of them."""
        return self._proposer_of_path()

    @property
    def speculation_shed(self) -> bool:
        return self._spec_shed

    @property
    def ragged_shed(self) -> bool:
        return self._ragged_shed

    def set_speculation_shed(self, shed: bool) -> None:
        """Degradation-controller actuator: clamp every draft window to
        width 1 so steps run the eager-equivalent width-1 verify — no
        draft dispatches, greedy tokens unchanged. Engaging it drops
        per-sequence proposer state through the ``_active_proposer``
        release path (stale draft caches must not survive the gap);
        Medusa/EAGLE re-seed incrementally on release, exactly like
        after an eviction. Fully reversible; a no-op without a
        proposer."""
        shed = bool(shed)
        if shed == self._spec_shed:
            return
        self._spec_shed = shed
        proposer = self._active_proposer
        if shed and proposer is not None and self.seqs:
            proposer.forget(list(self.seqs))

    def set_ragged_shed(self, shed: bool) -> None:
        """Degradation-controller actuator: route steps through the
        two-phase (chunk dispatch + decode/verify dispatch) template
        instead of the unified ragged dispatch — see :meth:`step`.
        Reversible; a no-op without ``ragged=True``."""
        self._ragged_shed = bool(shed)

    @property
    def adapter_shed(self) -> bool:
        return self._adapter_shed

    def set_adapter_shed(self, shed: bool) -> None:
        """Degradation-controller actuator: admit NEW adapter-tagged
        requests as base-model rows — no pool acquire, so the degraded
        engine spends zero swap H2D traffic and zero adapter-churn risk
        while burning. Already-running rows keep their pinned slots and
        finish under their adapter (a mid-stream model switch would be
        worse than the overload); shed admissions get their meta mapping
        annotated ``lora_shed=True`` so consumers can tell the degraded
        streams apart. Reversible; a no-op without a lora_pool."""
        self._adapter_shed = bool(shed)

    def _proposer_of_path(self):
        if self._spec is not None:
            return self._spec.proposer
        if self._ragged is not None:
            return self._ragged.proposer
        return None

    def _append_token(self, st: _SeqState, tok: int):
        st.last_token = tok
        st.tokens.append(tok)

    def _scratch_for(self, live: Sequence[int]) -> _PagedScratch:
        app = self.app
        pad_to = autobucketing.get_target_bucket(app.batch_buckets,
                                                 len(live), kind="batch")
        width = app._bt_width_for(live)
        if app.state_slots:
            pad_to = app.state_slots   # the rows of the step ARE the slots
        scr = self._scratch
        if (scr is None or scr.live != tuple(live) or scr.pad_to != pad_to
                or scr.width != width):
            kw = dict(seeds=[_meta_seed(self.seqs[s].meta) for s in live],
                      aids=self._lora_aids(live))
            if app.state_slots:
                scr = _SlotScratch(live, [self._state_slot[s] for s in live],
                                   pad_to, width,
                                   app.kv_mgr.spec.block_size, **kw)
            else:
                scr = _PagedScratch(live, pad_to, width,
                                    app.kv_mgr.spec.block_size, **kw)
            self._scratch = scr
        return scr

    def _state_on_kernel(self, shape) -> bool:
        """Whether the paged program of ``shape``, once it has been
        dispatched, steps its recurrent state on the state-step kernel:
        read from the record its trace left (the rule itself lives in
        ssm.state_kernel_declined alone), once a program shape."""
        on = self._state_kernel_shapes.get(shape)
        if on is None:
            on = self._state_kernel_shapes[shape] = \
                kernel_mode.state_on_kernel(
                    self.app.paged_program_notes(*shape))
        return on

    def _count_select(self, shape) -> None:
        """A dispatch of the paged program of ``shape`` of a stack with a
        learned sparse selection, once issued: one more
        ``sparse_dispatches``, and one more
        ``sparse_dispatches_select_kernel`` where the program's engagement
        record says its selection ran on the kernel (the rule itself lives
        in ops/index_select.declined alone), read once a program shape."""
        on = self._select_kernel_shapes.get(shape)
        if on is None:
            on = self._select_kernel_shapes[shape] = \
                kernel_mode.select_on_kernel(
                    self.app.paged_program_notes(*shape))
        self.host_stats["sparse_dispatches"] += 1
        self.host_stats["sparse_dispatches_select_kernel"] += on

    def _dispatch_decode(self, scr: _PagedScratch, toks_dev=None):
        """Issue ONE paged decode step to the device without materializing
        any output (region lint: nxdi_lint host-sync pass). ``toks_dev``:
        previous dispatch's on-device tokens (pipelined feedback); None =
        host tokens from the scratch buffer."""
        ids = scr.ids if toks_dev is None else toks_dev
        kw = {"row_seeds": scr.seeds}
        if scr.aids is not None:
            kw["adapter_ids"] = scr.aids
        if self.app._steady_state:
            # attribute any unexpected recompile to the batched requests'
            # trace lanes (serving/warmup.py steady-state discipline)
            with self.app.request_context(self._traces_of(scr.live)):
                out = self.app._run_paged(ids, scr.pos, scr.slots, scr.bt,
                                          scr.last, **kw)
        else:
            out = self.app._run_paged(ids, scr.pos, scr.slots, scr.bt,
                                      scr.last, **kw)
        _async_fetch(out["tokens"])
        if "moe_tally" in out:
            _async_fetch(out["moe_tally"])
        self.host_stats["dispatches"] += 1
        self.host_stats["device_steps"] += 1
        if self._state_on_kernel(scr.ids.shape):
            self.host_stats["dispatches_state_kernel"] += 1
        if self._ring_pages:
            self._count_window_pool()
        if self._sparse is not None:
            self._count_sparse()
            self._count_select(scr.ids.shape)
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("dispatch.decode", cat="adapter",
                        engine=self.engine_name, rows=scr.b,
                        pad_to=scr.pad_to, seq_ids=list(scr.live),
                        pipelined=toks_dev is not None)
        return out

    def _run_many(self, live: List[int], num_steps: int):
        """Fused k-step paged decode (model_base.paged_decode_loop): blocks
        for the whole horizon are pre-allocated, slot mappings advance
        IN-GRAPH — one dispatch, one fetch, zero per-token host work."""
        app = self.app
        if app.state_slots:
            from ..models.model_base import recurrent_refusal
            self._rollback_step_growth(live, num_steps)
            raise ConfigurationError(
                recurrent_refusal(["fused decode loop"])
                + " — step_many() is that loop; call step()")
        b = len(live)
        pad_to = autobucketing.get_target_bucket(app.batch_buckets, b,
                                                 kind="batch")
        bt = app.kv_mgr.block_table_array(live, app._bt_width_for(live))
        first = np.empty((b,), np.int32)
        pos = np.empty((b,), np.int32)
        seeds = np.empty((b,), np.int32)
        for i, s in enumerate(live):
            st = self.seqs[s]
            first[i] = st.last_token
            pos[i] = st.position
            seeds[i] = _meta_seed(st.meta)
        aids = self._lora_aids(live)
        if aids is not None:
            aids = np.asarray(aids, np.int32)
        if pad_to > b:
            first = _repeat_row0(first, pad_to)
            pos = _repeat_row0(pos, pad_to)
            bt = _repeat_row0(bt, pad_to)
            seeds = _repeat_row0(seeds, pad_to)
            if aids is not None:
                aids = _repeat_row0(aids, pad_to)
        kw = {"row_seeds": seeds}
        if aids is not None:
            kw["adapter_ids"] = aids
        cache_before = app.cache
        try:
            if _FAULTS.active:
                _FAULTS.fire("decode_step")
            if app._steady_state:
                with app.request_context(self._traces_of(live)):
                    out = app._run_paged_loop(first, pos, bt, num_steps,
                                              **kw)
            else:
                out = app._run_paged_loop(first, pos, bt, num_steps, **kw)
            self.host_stats["dispatches"] += 1
            self.host_stats["device_steps"] += num_steps
            rec = _get_recorder()
            if rec.enabled:
                rec.instant("dispatch.decode_loop", cat="adapter",
                            engine=self.engine_name, rows=b, pad_to=pad_to,
                            steps=num_steps, seq_ids=list(live))
            toks = self._fetch_rows(out, b)
        except ServingError:
            self._rollback_step_growth(live, num_steps)
            raise
        except Exception as e:
            self._rollback_step_growth(live, num_steps)
            self.telemetry.on_step_failure("decode", self._tenant_of(live))
            raise _trace_error(StepFailure(
                "fused paged decode loop failed; KV growth was rolled back "
                "and positions were not advanced",
                phase="decode", seq_ids=tuple(live),
                retry_safe=app.cache is cache_before)) from e
        return toks, pad_to

    # -- scheduler hooks ---------------------------------------------------
    @property
    def running_ids(self) -> Tuple[int, ...]:
        """seq_ids with a decodable row (prefill finished), sorted."""
        return tuple(sorted(self.seqs))

    @property
    def pending_prefill_ids(self) -> Tuple[int, ...]:
        """seq_ids admitted but still mid-prefill (deferred/chunked
        admissions), in admission order."""
        return tuple(sorted(self._chunks,
                            key=lambda s: self._chunks[s].admit_idx))

    @property
    def free_capacity(self) -> int:
        """Batch slots an ``add_requests`` call could still admit into
        (running + pending rows count against the compiled batch)."""
        return self.batch - len(self.seqs) - len(self._chunks)

    # -- post-mortem snapshot ----------------------------------------------
    def debug_state(self) -> Dict[str, Any]:
        """Read-only host-side snapshot for post-mortems (surfaced through
        :meth:`~..engine.scheduler.ServingEngine.dump_debug_state` and the
        ``GET /v1/debug/state`` endpoint). JSON-able; never touches device
        state. Running rows and the step in flight, then pending chunked
        admissions with prefill progress, batch headroom, block-pool
        occupancy (incl. unwritten-block tracking) and uncollected
        preemption records."""
        state = {
            "engine": self.engine_name,
            "running_ids": [int(s) for s in sorted(self.seqs)],
            "positions": {int(s): int(st.position)
                          for s, st in self.seqs.items()},
            "tenants": {int(s): _meta_tenant(st.meta)
                        for s, st in self.seqs.items()},
            "pipeline_inflight": (0 if self._inflight is None
                                  else len(self._inflight.live)),
            "ready_undelivered": [int(s) for s in sorted(self._ready)],
            # prompts whose last chunk is dispatched and not waited for
            "prefill_parked": [int(s) for p in self._parked
                               for _, s, cst in p.finals
                               if self._chunks.get(s) is cst],
            "host_stats": dict(self.host_stats),
        }
        mgr = self.app.kv_mgr
        usable = mgr.spec.num_blocks - 1          # block 0 is the null block
        free = int(mgr.allocator.num_free)
        state.update({
            "pending_prefill": {
                int(s): {"done": int(c.done), "total": len(c.prompt),
                         "tenant": _meta_tenant(c.meta)}
                for s, c in self._chunks.items()},
            "free_capacity": self.free_capacity,
            "blocks": {"usable": usable, "free": free,
                       "in_use": usable - free,
                       "unwritten": len(self._unwritten)},
            "preempted_uncollected": [int(r.seq_id) for r in self.preempted],
            "ragged": self._ragged is not None,
        })
        if self._lora_pool is not None:
            state["lora"] = {
                "rows": {int(s): int(slot)
                         for s, slot in self._lora_slots.items()},
                "shed": self._adapter_shed,
                "pool": self._lora_pool.debug_state(),
            }
        return state
    def prefix_warmth(self, prompt: Sequence[int],
                      adapter: Optional[str] = None) -> int:
        """READ-ONLY probe: how many leading tokens of ``prompt`` an
        admission right now would serve from the prefix cache. Peeks the
        :class:`~..modules.block_kv_cache.BlockKVCacheManager` hash state
        without taking references or touching LRU order, and cuts the
        count at the first block whose writer has not landed yet (pending
        chunked admissions) — exactly the cut a real admission would
        apply. Schedulers use it to order admission batches warm-first;
        capped at ``len(prompt) - 1`` like admission itself (the final
        token always runs to produce the first sample). With a host KV
        spill tier attached, consecutive spilled full blocks past the
        device hit count as warm too (an admission would restore, not
        recompute, them) — the fleet router's affinity signal.

        ``adapter`` (optional, the request's named LoRA adapter) extends
        the signal with adapter residency: when a pool is attached and
        the adapter is already device-resident, the admission saves one
        swap's worth of H2D traffic, valued as
        ``prefill_chunk_tokens`` warm tokens (a swap stall is on the
        order of a chunk dispatch) so the router lands a tenant's
        requests where their adapter lives. Read-only both ways — the
        residency probe never touches the pool's LRU order."""
        from ..modules.block_kv_cache import cut_cached_at_unwritten
        cached, blocks = self.app.kv_mgr.probe_cached_tokens(prompt)
        if cached and self._unwritten:
            cached = cut_cached_at_unwritten(
                blocks, cached, self.app.kv_mgr.spec.block_size,
                self._unwritten)
        if self._kv_tier is not None:
            cached = self._tier_warmth(prompt, cached)
        warmth = min(cached, len(prompt) - 1)
        if (adapter is not None and self._lora_pool is not None
                and self._lora_pool.resident(adapter)):
            warmth += self.prefill_chunk_tokens
        return warmth

    # -- host-RAM KV spill tier (serving/fleet/kv_tier.py) -----------------
    def _spill_block(self, blk: int, content_hash: bytes) -> None:
        """Manager eviction hook: copy an LRU-evicted prefix block's
        payload device→host into the spill tier, keyed by its content
        chain hash. Best-effort by contract — a failure (including the
        ``kv_spill`` fault point) is swallowed and counted, never failing
        the allocation whose eviction triggered it. Skips blocks whose
        registered hash never had its content land (``_unwritten``)."""
        if blk in self._unwritten:
            return
        try:
            cache = self.app.cache
            self._kv_tier.spill(content_hash,
                                np.asarray(cache["k"][:, blk]),
                                np.asarray(cache["v"][:, blk]))
            self.host_stats["kv_spilled_blocks"] += 1
        except Exception:
            self._kv_tier.stats["spill_errors"] += 1

    def _tier_warmth(self, prompt: Sequence[int], cached: int) -> int:
        """Extend the device prefix-hit count with consecutive spilled
        full blocks an admission right now would restore instead of
        recompute (read-only; no recency touch)."""
        from ..modules.block_kv_cache import _hash_block
        bs = self.app.kv_mgr.spec.block_size
        parent = b""
        warm = cached
        for bi in range(len(prompt) // bs):
            parent = _hash_block(parent, list(prompt[bi * bs:(bi + 1) * bs]))
            if (bi + 1) * bs <= cached:
                continue                   # device-cached already
            if bi * bs != warm or not self._kv_tier.contains(parent):
                break
            warm = (bi + 1) * bs
        return warm

    def _restore_spilled(self, sid: int, prompt: Sequence[int],
                         blocks: Sequence[int], done: int) -> int:
        """Walk the prompt's full-block chain hashes past the (post-cut)
        device prefix hit through the spill tier; consecutive hits are
        re-admitted by ONE batched async H2D write and their tokens
        skipped from recompute-prefill. Returns the new ``done`` count
        (capped at ``len(prompt) - 1`` like prefix hits — the final token
        always runs to produce the first sample; a restored block that
        covers it is partially rewritten with identical values by the
        final chunk). The ``kv_restore`` fault point fires BEFORE the
        device write, so the transactional admission rollback is exact."""
        from ..modules.block_kv_cache import _hash_block
        tier = self._kv_tier
        bs = self.app.kv_mgr.spec.block_size
        limit = len(prompt) - 1
        parent = b""
        restores: List[Tuple[int, Any]] = []
        new_done = done
        for bi in range(len(prompt) // bs):
            parent = _hash_block(parent,
                                 list(prompt[bi * bs:(bi + 1) * bs]))
            if (bi + 1) * bs <= new_done:
                continue                   # device-cached already
            if bi * bs != new_done or new_done >= limit:
                break                      # mid-block cap or gap: stop
            payload = tier.get(parent)
            if payload is None:
                break
            restores.append((blocks[bi], payload))
            new_done = min((bi + 1) * bs, limit)
        if not restores:
            return done
        if _FAULTS.active:
            _FAULTS.fire("kv_restore")
        self._apply_block_payloads(restores)
        n_tok = new_done - done
        tier.note_restored(len(restores), n_tok)
        self.host_stats["kv_restored_blocks"] += len(restores)
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("kv.restore", cat="fleet", engine=self.engine_name,
                        seq_id=int(sid), blocks=len(restores),
                        tokens=n_tok)
        return new_done

    def _apply_block_payloads(self, restores) -> None:
        """One batched (asynchronously dispatched) H2D write placing
        spilled payloads into their freshly-allocated device blocks. The
        rebound cache feeds every subsequent dispatch, so the call's
        first materialized fetch orders after (and thereby confirms) the
        restore writes — a deferred device failure here surfaces at that
        fetch and rolls the admission back like any chunk failure."""
        idx = np.asarray([b for b, _ in restores], np.intp)
        k = np.stack([np.asarray(p["k"]) for _, p in restores], axis=1)
        v = np.stack([np.asarray(p["v"]) for _, p in restores], axis=1)
        cache = self.app.cache
        self.app.cache = {"k": cache["k"].at[:, idx].set(k),
                          "v": cache["v"].at[:, idx].set(v)}

    # -- multi-LoRA adapter pool (serving/lora_pool.py) --------------------
    def _bind_adapter(self, sid: int, meta: Any) -> None:
        """Resolve a request's named adapter (meta "adapter" key) to a
        pinned device slot at admission. With the ``shed_adapters``
        degradation actuator engaged the request is admitted as a
        base-model row instead — no acquire, no swap H2D traffic — and
        its meta mapping is annotated ``lora_shed=True`` so the stream's
        consumer can tell the degraded output apart. A typed acquire
        failure (CapacityError: every slot pinned; StepFailure: the swap
        itself failed, rolled back) propagates into the admission's
        transactional rollback — nothing is admitted."""
        if self._lora_pool is None:
            return
        name = _meta_adapter(meta)
        if name is None:
            return
        if self._adapter_shed:
            self.host_stats["lora_shed_requests"] += 1
            try:
                meta["lora_shed"] = True
            except TypeError:
                pass
            return
        slot = self._lora_pool.acquire(name)
        self._lora_slots[sid] = slot
        self._lora_names[sid] = name
        self.host_stats["lora_rows"] += 1

    def _lora_release(self, sid: int) -> None:
        """Unpin ``sid``'s adapter slot (release / preemption / admission
        rollback). Idempotent — rollback paths release blindly."""
        name = self._lora_names.pop(sid, None)
        if name is not None:
            self._lora_slots.pop(sid, None)
            self._lora_pool.release(name)

    def _lora_aids(self, sids) -> Optional[List[int]]:
        """Per-row device slots for a dispatch, or None without a pool —
        the adapter_ids kwarg is only ever passed when a pool is
        attached, keeping no-pool graphs byte-identical. Base-model rows
        (no adapter, or admitted shed) gather slot 0, the pinned zero
        adapter."""
        if self._lora_pool is None:
            return None
        return [self._lora_slots.get(s, 0) for s in sids]

    # -- preemption -------------------------------------------------------
    def preempt(self, seq_id: int, reason: str = "scheduler") -> Preempted:
        """Scheduler-driven eviction of one running or pending sequence:
        its blocks are reclaimed (never-written blocks invalidated, not
        freed as servable) and the :class:`Preempted` record — tokens so
        far, remaining deadline, meta passthrough — is returned AND queued
        for :meth:`take_preempted`. A pipelined in-flight token for the
        victim is dropped (the requeue replay regenerates it, same as
        pressure preemption). Raises :class:`SequenceStateError` for an
        unknown/released seq_id."""
        if seq_id not in self.seqs and seq_id not in self._chunks:
            raise SequenceStateError(
                f"cannot preempt seq_id {seq_id}: not running or pending")
        self._preempt(seq_id, reason)
        return self.preempted[-1]

    def take_preempted(self) -> List[Preempted]:
        """Drain :class:`Preempted` records accumulated since the last
        call. The engine re-queues each ``record.tokens`` as a new prompt;
        under greedy sampling the recomputed continuation is bit-identical
        to the uninterrupted run (a token still in the pipeline when its
        sequence is preempted is regenerated by the replay)."""
        out, self.preempted = self.preempted, []
        return out

    def _choose_victim(self, exclude=frozenset()) -> Optional[int]:
        if self.preemption_policy is None:
            return None
        cands = [(sid, st.admit_idx, len(st.tokens) - st.prompt_len)
                 for sid, st in self.seqs.items()
                 if st.running and sid not in exclude]
        # pending chunked admissions are victims too (zero generated
        # tokens: they lose the least decode work of anything live)
        cands += [(sid, cst.admit_idx, 0)
                  for sid, cst in self._chunks.items()
                  if sid not in exclude]
        return pick_victim(self.preemption_policy, cands)

    def _preempt(self, victim: int, reason: str):
        self._ready.pop(victim, None)      # replay regenerates it
        self._lora_release(victim)         # requeue re-acquires via meta
        proposer = self._active_proposer
        if proposer is not None:
            # stateful proposers (Medusa/EAGLE) must not carry the
            # victim's features into a re-admission under the same id
            proposer.forget((victim,))
        cst = self._chunks.pop(victim, None)
        if cst is not None:
            # half-prefilled victim: blocks not fully written must leave
            # the prefix cache (abort, not a plain free); the record's
            # tokens are the bare prompt — nothing was generated yet
            self._free_state_slot(victim, event="preempt")
            self._abort_pending(victim)
            tenant = _meta_tenant(cst.meta)
            self.preempted.append(Preempted(
                seq_id=victim, tokens=tuple(cst.prompt),
                prompt_len=len(cst.prompt), n_generated=0, reason=reason,
                deadline=cst.deadline, meta=cst.meta,
                trace_id=self._trace_preempt(victim, reason, tenant,
                                             pending=True,
                                             trace=_trace_of(cst.meta))))
            self.telemetry.on_preempt(victim, reason, tenant)
            return
        st = self.seqs.pop(victim)
        self._scratch = None               # victim's blocks are reclaimed
        self._note_stale("preempt")
        # recompute preemption re-prefills from position 0, which resets
        # whatever slot the requeue is given
        self._free_state_slot(victim, event="preempt")
        if victim in self.app.kv_mgr.tables:
            self.app.kv_mgr.end_sequence(victim)
        tenant = _meta_tenant(st.meta)
        self.preempted.append(Preempted(
            seq_id=victim, tokens=tuple(st.tokens),
            prompt_len=st.prompt_len,
            n_generated=len(st.tokens) - st.prompt_len, reason=reason,
            deadline=st.deadline, meta=st.meta,
            trace_id=self._trace_preempt(victim, reason, tenant,
                                         trace=_trace_of(st.meta))))
        self.telemetry.on_preempt(victim, reason, tenant)

    def _trace_preempt(self, victim: int, reason: str, tenant: str,
                       pending: bool = False,
                       trace: Optional[str] = None) -> Optional[str]:
        rec = _get_recorder()
        if not rec.enabled:
            return None
        return rec.instant("preempt", cat="adapter",
                           engine=self.engine_name, seq_id=victim,
                           reason=reason, tenant=tenant, pending=pending,
                           trace=trace)

    def _grow_for_step(self, live: Sequence[int],
                              n: int = 1) -> List[int]:
        """Grow every live row's block list by ``n`` tokens, evicting
        victims per the policy when the pool is dry. Returns the rows
        still live (preempted ones removed). If eviction cannot free
        enough, all growth from this call is rolled back and the
        :class:`CapacityError` propagates."""
        app = self.app
        live = list(live)
        queue = list(live)
        grown: List[int] = []
        while queue:
            s = queue[0]
            try:
                app.kv_mgr.grow(s, n)
            except CapacityError:
                victim = self._choose_victim()
                if victim is None:
                    for g in grown:
                        app.kv_mgr.shrink(g, n)
                    raise
                self._preempt(victim, reason="grow")
                for lst in (queue, live, grown):
                    if victim in lst:
                        lst.remove(victim)
                continue
            queue.pop(0)
            grown.append(s)
        return live

    def _rollback_step_growth(self, live: Sequence[int], n: int = 1):
        for s in live:
            self.app.kv_mgr.shrink(s, n)

    def _rollback_admission(self, seq_ids: Sequence[int]):
        """Abort every sequence begun by the failing add_requests call:
        frees its blocks and purges never-written content hashes from the
        prefix cache (the free count is restored exactly; prefix-HIT
        blocks whose content predates the call stay resident). Sequences
        that already finished their prefill inside the call are unwound
        too — admission is all-or-nothing.

        Reverse admission order matters: when prompts within the call
        share a prefix, later sequences prefix-HIT blocks the first one
        allocated (and hashed) moments earlier — unwinding in reverse
        makes the ORIGINATING sequence's abort the last dereference, so
        its invalidate (not a later sibling's plain free) retires the
        never-written hash."""
        for sid in reversed(list(seq_ids)):
            self._chunks.pop(sid, None)
            self._ready.pop(sid, None)
            self._lora_release(sid)
            if self.seqs.pop(sid, None) is not None:
                self._scratch = None
            self._abort_pending(sid)
        self.telemetry.on_admission_rollback()

    # -- the window layers' pool (stacks with a window pool) ---------------
    def window_pool_rows(self):
        """Over the running rows, a window layer's share of the pool by
        layer kind: pages their rings hold (a row's pages up to the
        ring's), pages the layer would hold at full length, the rows'
        tokens inside the window, and all of them."""
        bs = self.app.kv_mgr.spec.block_size
        ring, window = self._ring_pages, self.app.spec.sliding_window
        held = full = in_window = running = 0
        for st in self.seqs.values():
            n = int(st.position)
            pages = -(-n // bs)
            held += min(pages, ring)
            full += pages
            in_window += min(n, window)
            running += n
        return held, full, in_window, running

    def _count_window_pool(self) -> None:
        """At a decode dispatch: :meth:`window_pool_rows` over the window
        layers, pages held and pages at full length summed into
        ``host_stats`` (``kv_window_pages_held`` / ``_unwindowed``), the
        tokens as gauges (``kv_tokens_in_window`` / ``kv_tokens_running``:
        what a decode step's window layers and global layers must read)."""
        spec = self.app.spec
        held, full, in_window, running = self.window_pool_rows()
        layers = spec.num_window_layers
        stats = self.host_stats
        stats["kv_window_pages_held"] += layers * held
        stats["kv_window_pages_unwindowed"] += layers * full
        stats["kv_tokens_in_window"] = in_window
        stats["kv_tokens_running"] = running
        reg = self.telemetry.registry
        if reg.enabled:
            gauge = tmetrics.kv_pool_pages_gauge(reg)
            gauge.set(layers * held, engine=self.engine_name, kind="window")
            gauge.set((spec.num_attn_layers - layers) * full,
                      engine=self.engine_name, kind="global")

    # -- a learned sparse selection (stacks with an indexer) ---------------
    def _count_sparse(self) -> None:
        """At a decode dispatch, over the running rows and from their
        lengths alone (exact, no device read): the tokens the step's
        queries select, ``min(len, topk)`` a row, and the tokens they hold
        (``sparse_tokens_selected`` / ``sparse_tokens_cached``, summed);
        and the pages the rows hold in the index-key pool, a page a layer
        (``kv_index_pages_held``, a gauge, and the ``index`` kind of
        ``nxdi_kv_pool_pages``)."""
        topk = self._sparse.topk
        bs = self.app.kv_mgr.spec.block_size
        selected = cached = pages = 0
        for st in self.seqs.values():
            n = int(st.position) + 1          # the step's own token with it
            selected += min(n, topk)
            cached += n
            pages += -(-n // bs)
        stats = self.host_stats
        stats["sparse_tokens_selected"] += selected
        stats["sparse_tokens_cached"] += cached
        stats["kv_index_pages_held"] = pages * self.app.spec.num_attn_layers
        reg = self.telemetry.registry
        if reg.enabled:
            tmetrics.sparse_tokens_counter(reg).inc(
                selected, engine=self.engine_name, kind="selected")
            tmetrics.sparse_tokens_counter(reg).inc(
                cached, engine=self.engine_name, kind="cached")
            tmetrics.kv_pool_pages_gauge(reg).set(
                stats["kv_index_pages_held"], engine=self.engine_name,
                kind="index")

    # -- recurrent-state slots (recurrent/hybrid stacks) -------------------
    def _take_state_slot(self, sid: int) -> None:
        """Give ``sid`` the lowest free state slot (no-op on an attention
        stack). Admission is capped at the batch, so a slot is always
        free; the slot is NOT cleared — the sequence's first chunk starts
        at position 0, which resets it in the step graph."""
        if not self.app.state_slots:
            return
        self._state_slot[sid] = self._state_free.pop(0)
        self.host_stats["state_slot_allocs"] += 1
        self._note_state_slots("alloc")

    def _free_state_slot(self, sid: int, event: str = "free") -> None:
        slot = self._state_slot.pop(sid, None)
        if slot is None:
            return
        bisect.insort(self._state_free, slot)
        self.host_stats["state_slot_frees"] += 1
        self._note_state_slots(event)

    def _note_state_slots(self, event: str) -> None:
        live = len(self._state_slot)
        self.host_stats["state_slots_live"] = live
        reg = self.telemetry.registry
        if reg.enabled:
            tmetrics.state_slot_events_counter(reg).inc(
                engine=self.engine_name, event=event)
            gauge = tmetrics.state_slots_gauge(reg)
            gauge.set(live, engine=self.engine_name, state="live")
            gauge.set(len(self._state_free), engine=self.engine_name,
                      state="free")

    # -- chunked, packed, schedulable prefill ------------------------------
    def _pending_ids(self):
        """seq_ids admitted but still mid-prefill (chunked admissions)."""
        return self._chunks.keys()

    def _advance_prefill(self, seq_ids=None, park: bool = False,
                         decoding: bool = False):
        """Run the packed prefill-chunk dispatches that go before this
        call's decode work, back to back with no fetch between them;
        finished sequences' first tokens land in ``_ready``. How many is
        :func:`chunks_before_step`'s answer: ONE of at most
        ``prefill_budget_tokens`` tokens under the budget; without it the
        whole pending chain where no row is ``decoding`` (or the window of
        decode gaps has not filled), else ``k`` dispatches read from the
        prefill load of the last ``_PACE_WINDOW`` decode gaps.
        ``seq_ids`` is the step call's explicit target set (None = all):
        an expired pending admission outside it is skipped, not raised —
        a healthy row must not be stalled by an unrelated request's
        budget. ``park``: see :meth:`_prefill_step` (a whole chain is never
        parked).

        Returns whether the call's decode step is HELD BACK (the lookahead's
        call only, paced): a chain's first chunk went out in front of a step
        still in flight, so the caller fetches that step and enqueues none.
        The next step would run behind the chunk whenever it is enqueued,
        the chunk keeps the device busy through the fetch, and the tokens in
        flight are not kept waiting while the host issues the rest of the
        chain: the next call issues up to ``k - 1`` more with nothing to
        fetch, then the step. Enqueued in front of the fetch, as a budgeted
        chunk is, ``k`` chunks' host time made the step in flight late as
        well as the one behind them: two long gaps a chain for one (PERF.md
        section 6, PR 63)."""
        if not self._chunks:
            return False
        budget = self.prefill_budget_tokens
        gaps = len(self._pace_gaps)
        k = chunks_before_step(gaps, self._pace_sum, decoding,
                               budget is not None, self._pace_gaps.maxlen)
        st = self.host_stats
        st["prefill_pace_k"] = k or 0
        st["prefill_pace_f"] = self._pace_sum / gaps if gaps else 0.0
        paced = budget is None and k is not None
        # a paced chain that finds the lookahead's step in flight LEADS with
        # one chunk and holds the next step back (see the return value)
        lead = paced and park and self._inflight is not None
        room = (None if k is None else k if not paced
                else 1 if lead else k - self._chain_issued)
        n = 0
        try:
            # a whole chain is fetched at once, as the blocking admission's
            while (room is None or n < room) and self._prefill_step(
                    budget=budget, target=seq_ids,
                    park=park and k is not None):
                n += 1
        finally:
            if paced:
                self._chain_issued += n
            if budget is None and n:
                self._note_paced(n, whole=k is None)
        return lead and n > 0

    def _rows_after_chain(self, live: List[int], seq_ids) -> List[int]:
        """The rows of a step call's decode work once its chunks have run
        (``step_ahead()`` and the speculative step). Where no budget is set
        and a whole chain just ran, it was fetched too (nobody decoding, the
        window not full): the prompts it finished decode from this very call,
        as they did in the pass of a blocking admission. Under the budget a row's
        first step is the next call's, as it always was; so it is in
        ``step()``, whose return holds one token a row, and in
        ``step_many()``, whose horizon the caller sized without the row."""
        if self.prefill_budget_tokens is not None or not self.seqs:
            return live
        rows = _live_rows(self.seqs, seq_ids, self._pending_ids())
        if len(rows) > len(live):
            # rows a whole chain just finished (only a whole chain is
            # fetched inside the call that ran it)
            _pre_step_checks(self.seqs, [s for s in rows if s not in live],
                             self._pos_limit, self.telemetry)
        return rows

    def _note_paced(self, chunks: int, whole: bool):
        st = self.host_stats
        if whole:
            st["prefill_chains_whole"] += 1
        else:
            st["prefill_paced_passes"] += 1
            st["prefill_paced_chunks"] += chunks
        self.telemetry.on_paced(chunks, whole, st["prefill_pace_k"],
                                st["prefill_pace_f"])

    def _prefill_step(self, budget: Optional[int] = None, only=None,
                      target=None,
                      token_at: Optional[Dict[int, float]] = None,
                      park: bool = False) -> bool:
        """ONE packed chunk dispatch: pending sequences (admission order)
        each contribute their next uncached-suffix chunk as a ragged row
        of a single ctx-bucket ``_run_paged`` call, bounded by ``budget``
        real prompt tokens (None = unbounded). Sequences whose FINAL chunk
        lands graduate to running rows with their first token stashed in
        ``_ready``; intermediate samples are discarded. A dispatch failure
        rolls every sequence packed in THIS dispatch back
        (:meth:`~..modules.block_kv_cache.BlockKVCacheManager.abort_sequence`)
        and raises a typed :class:`StepFailure`. ``token_at`` (the
        transactional add_requests path) suppresses per-sequence admission
        telemetry — it takes each graduating sequence's first-token instant
        instead, and the caller reports the whole call only once it is past
        rollback. ``target`` is the step call's explicit seq_ids set (None
        = all): an expired pending admission is raised only when targeted,
        merely skipped from packing otherwise.

        ``park`` (the lookahead's call, with a decode step to hide behind):
        a dispatch that holds a prompt's FINAL chunk is not waited for
        either. Its async fetch is started and its rows are parked
        (:class:`_Parked`): they stay pending admissions until
        :meth:`_graduate` finds their token host-visible behind a later
        decode step's fetch. Every other caller materialises by contract,
        and first graduates whatever an earlier call parked.

        Returns whether a dispatch went out (``False``: no pending sequence
        has a chunk left to run)."""
        if self._parked and not park:
            self._graduate()
        chunks = self._chunks
        order = sorted(chunks, key=lambda s: chunks[s].admit_idx)
        if only is not None:
            order = [s for s in order if s in only]
        now = time.perf_counter()
        expired = [s for s in order if chunks[s].deadline is not None
                   and now >= chunks[s].deadline]
        if expired:
            hit = (expired if target is None
                   else [s for s in expired if s in set(target)])
            if hit:
                fresh = [s for s in hit if not chunks[s].expired_reported]
                for s in fresh:
                    chunks[s].expired_reported = True
                self.telemetry.on_deadline(
                    fresh, [_meta_tenant(chunks[s].meta) for s in fresh])
                raise _trace_error(DeadlineExceeded(
                    f"seq_ids {hit} exceeded their wall-clock deadline "
                    "mid-prefill; release() them (or re-queue with a fresh "
                    "budget) and step again", seq_ids=hit))
            # expired but not targeted by this step: don't burn budget on
            # them, and don't stall the targeted healthy rows
            order = [s for s in order if s not in expired]
        rows: List[Tuple[int, int, int, bool]] = []
        left = float("inf") if budget is None else int(budget)
        for s in order:
            if len(rows) == self.batch or left < 1:
                break
            st = chunks[s]
            if st.done == len(st.prompt):
                continue               # parked: no chunk left to run
            n = int(min(len(st.prompt) - st.done,
                        self.prefill_chunk_tokens, left))
            rows.append((s, st.done, n, st.done + n == len(st.prompt)))
            left -= n
        if not rows:
            return False
        r_min = self.app.prefill_row_buckets[0]
        if r_min < len(rows) and 2 * len(rows) < self.batch:
            # a full-batch pack that would be less than half real rows goes
            # r_min rows at a time instead, in admission order: the pack
            # computes every row of the batch whatever it carries, and at
            # two prompts of 32 rows it held every decoding row for a
            # dozen one-row chunks' time (PERF.md section 6, PR 36)
            rows = rows[:r_min]
        seq_list = tuple(s for s, *_ in rows)
        final_rows = [(i, s) for i, (s, _, _, fin) in enumerate(rows)
                      if fin]
        # tenant attribution captured BEFORE any rollback pops the chunk
        # state (failure counters + trace events need it afterwards)
        row_tenant = _common_tenant(_meta_tenant(chunks[s].meta)
                                    for s in seq_list)
        cache_before = self.app.cache
        rec = _get_recorder()
        for s in seq_list:
            # a prompt's wait for its turn ends where its FIRST chunk goes
            # to the device (later chunks find the stamp written)
            chunks[s].timeline.stamp("dispatch", now)
        # one slice over pack + dispatch + final-chunk fetch (a failed
        # dispatch closes it too; its error event follows on the timeline)
        span = rec.span(
            "dispatch.prefill_chunk", cat="adapter",
            engine=self.engine_name, seq_ids=list(seq_list),
            rows=len(rows), tokens=sum(n for _, _, n, _ in rows),
            final_seq_ids=[s for _, s in final_rows],
            tenant=row_tenant) if rec.enabled else rec.span(
                "dispatch.prefill_chunk")      # the shared no-op
        try:
            with span:
                if _FAULTS.active:
                    _FAULTS.fire("prefill_chunk")
                packed = self._pack_prefill_rows(rows)
                span.set(width=int(packed[0].shape[1]))
                out = self._dispatch_prefill_chunk(packed,
                                                   fetch=bool(final_rows))
                # materialize INSIDE the try (dispatch is asynchronous): a
                # genuine device failure surfacing at the fetch must still
                # be wrapped and rolled back here. Intermediate-only
                # dispatches fetch nothing — their samples are discarded
                # unmaterialized.
                new, t_token = (self._fetch_prefill_tokens(out)
                                if final_rows and not park else (None, None))
        except ServingError as e:
            self._abort_prefill_rows(seq_list)
            _trace_error(e)                # attach a timeline id in place
            raise
        except Exception as e:
            self._abort_prefill_rows(seq_list)
            self.telemetry.on_step_failure("prefill", row_tenant)
            raise _trace_error(StepFailure(
                "chunked prefill dispatch failed; every partially-"
                "prefilled sequence packed in it was rolled back",
                phase="prefill", seq_ids=seq_list,
                retry_safe=self.app.cache is cache_before)) from e
        for s, _, n, _ in rows:
            chunks[s].done += n
        out_rows = packed[-1]              # slot-ordered pack: row != i
        finals = [(i if out_rows is None else int(out_rows[i]), s, chunks[s])
                  for i, s in final_rows]
        if final_rows:
            covered = [(s2, cst, cst.done) for s2, cst in chunks.items()]
            if park:
                self._parked.append(_Parked(
                    out=out, no=self.host_stats["prefill_dispatches"],
                    finals=finals, covered=covered,
                    packed=[(s, chunks[s]) for s in seq_list]))
            else:
                self._confirm_written(covered)
        pad_rows, width = packed[0].shape
        real = sum(n for _, _, n, _ in rows)
        self.host_stats["prefill_real_tokens"] += real
        self.host_stats["prefill_padded_tokens"] += pad_rows * width
        # the tokens that ran the second decoder: where the program keeps it
        # apart (its own record says) one a row of a dispatch that samples
        # and none of one that does not, else every token
        through = 0
        if self._cross_decoder:
            self.host_stats["prefill_dispatches_sampled"] += bool(final_rows)
            through = (len(rows) * bool(final_rows)
                       if kernel_mode.second_decoder_apart(
                           self.app.paged_program_notes(pad_rows, width))
                       else real)
        if through:
            self.host_stats["prefill_tokens_cross_decoder"] += through
        self.telemetry.on_prefill_chunk(len(rows), pad_rows, real,
                                        pad_rows * width, through)
        if final_rows and not park:
            self._graduate_rows(finals, new, t_token, token_at)
        return True

    def _confirm_written(self, covered) -> None:
        """A chunk dispatch's tokens were MATERIALIZED, and the donated
        cache chain orders every earlier dispatch before it: the blocks
        ``covered`` lists ((seq_id, chunk state, tokens written as of that
        dispatch) of every pending admission then) are confirmed written.
        Unfetched dispatches confirm nothing: a genuine async device
        failure in one surfaces at a later fetch, and the rollback there
        must still find their blocks in ``_unwritten`` (or their
        allocate-time hashes would be freed as valid)."""
        bs = self.app.kv_mgr.spec.block_size
        for s, cst, done in covered:
            if self._chunks.get(s) is cst:
                self._unwritten.difference_update(
                    self.app.kv_mgr.tables[s][:done // bs])

    def _still_pending(self, packed) -> List[int]:
        """The seq_ids of ``packed`` ((seq_id, chunk state) pairs) that are
        still the pending admissions they were."""
        return [s for s, cst in packed if self._chunks.get(s) is cst]

    def _graduate_rows(self, finals, new, t_token: float,
                       token_at: Optional[Dict[int, float]] = None):
        """The prompts of ``finals`` ((output row, seq_id, chunk state))
        become running rows, their first token from ``new`` stashed in
        ``_ready``; one that left while its token was on its way is passed
        over."""
        rec = _get_recorder()
        for row, s, st in finals:
            if self._chunks.get(s) is not st:
                continue
            del self._chunks[s]
            self._unwritten.difference_update(self.app.kv_mgr.tables[s])
            tok = int(new[row, 0])
            self.seqs[s] = _SeqState(
                position=len(st.prompt), last_token=tok,
                tokens=list(st.prompt) + [tok],
                prompt_len=len(st.prompt), admit_idx=st.admit_idx,
                deadline=st.deadline, meta=st.meta)
            self._scratch = None   # live set grew; see add_requests note
            self._note_stale("admit")
            self._ready[s] = tok
            if st.timeline.stamp("token", t_token):
                rec.mark("request.token", _trace_of(st.meta))
            if token_at is not None:
                token_at[s] = t_token
            else:
                self.telemetry.on_add([s], [st.prompt], st.t0, [t_token],
                                      live=1, padded=1, count_rows=False,
                                      tenants=[_meta_tenant(st.meta)])

    def _graduate(self, behind: Optional[int] = None):
        """Graduate the rows of parked final chunks, oldest first.
        ``behind``: the ``chunks_before`` of a decode step whose tokens were
        just fetched, so every chunk up to that number has run and its
        tokens (async-prefetched) are host-visible for nothing. ``None``:
        every one, waited for — the callers with no decode step to hide
        behind, counted as a blocking fetch. A failure rolls every sequence
        packed in that dispatch back, as at the dispatch."""
        while self._parked and (behind is None
                                or self._parked[0].no <= behind):
            p = self._parked.pop(0)
            try:
                new, t_token = self._fetch_prefill_tokens(
                    p.out, waited=behind is None)
            except Exception as e:
                seq_list = tuple(self._still_pending(p.packed))
                tenant = _common_tenant(_meta_tenant(cst.meta)
                                        for _, cst in p.packed)
                self._abort_prefill_rows(seq_list)
                self.telemetry.on_step_failure("prefill", tenant)
                raise _trace_error(StepFailure(
                    "a final prefill chunk failed at its deferred fetch; "
                    "every partially-prefilled sequence packed in it was "
                    "rolled back", phase="prefill", seq_ids=seq_list,
                    retry_safe=False)) from e
            self._confirm_written(p.covered)
            self._graduate_rows(p.finals, new, t_token)

    def _pack_prefill_rows(self, rows):
        """Build the ragged packed-chunk inputs: one row per sequence,
        positions at each row's own suffix offset, slots through its own
        block table; width = smallest ctx bucket covering the longest
        chunk, rows = smallest rung of ``app.prefill_row_buckets`` covering
        the sequences packed (``r_min`` rows for one prompt, the full batch
        for more), padded by repeating row 0 (the usual invariant)."""
        from ..modules.block_kv_cache import slots_from_table
        app = self.app
        b = len(rows)
        width = autobucketing.get_target_bucket(
            self._chunk_widths, max(n for _, _, n, _ in rows), kind="ctx")
        sids = [s for s, *_ in rows]
        bt = app.kv_mgr.block_table_array(sids, app._bt_width_for(sids))
        ids_w = np.zeros((b, width), np.int32)
        pos_w = np.zeros((b, width), np.int32)
        slot_pos = np.full((b, width), -1, np.int32)
        # the token a row samples from; a row whose chunk is not its
        # prompt's last samples nothing, which a stack with a second decoder
        # is told by a negative index: its chunk program runs that decoder,
        # the head and the draw for the sampled token alone, and not at all
        # in a dispatch where no row samples
        # (model_base.second_decoder_tokens). Any other stack samples token
        # 0, which nobody fetches
        last = np.full((b,), self._no_sample, np.int32)
        for i, (s, lo, n, fin) in enumerate(rows):
            st = self._chunks[s]
            ids_w[i, :n] = st.prompt[lo:lo + n]
            pos_w[i] = lo + np.arange(width, dtype=np.int32)
            slot_pos[i, :n] = pos_w[i, :n]
            if fin:
                last[i] = n - 1
        slots = slots_from_table(bt, slot_pos, app.kv_mgr.spec.block_size)
        seeds = np.asarray([_meta_seed(self._chunks[s].meta) for s in sids],
                           np.int32)
        aids = self._lora_aids(sids)
        if aids is not None:
            aids = np.asarray(aids, np.int32)
        pad_to = autobucketing.get_target_bucket(app.prefill_row_buckets, b,
                                                 kind="prefill_rows")
        if app.state_slots:
            return self._pack_state_rows(pad_to, sids, ids_w, pos_w, slots,
                                         bt, last, seeds, aids)
        if pad_to > b:
            seeds = _repeat_row0(seeds, pad_to)
            if aids is not None:
                aids = _repeat_row0(aids, pad_to)
        return _pad_paged_rows(pad_to, ids_w, pos_w, slots, bt, last) \
            + (seeds, aids, None, None)

    def _pack_state_rows(self, pad_to, sids, ids_w, pos_w, slots, bt, last,
                         seeds, aids):
        """The packed chunk of a recurrent/hybrid stack. At the full batch
        the rows ARE the state slots: each sequence's row goes to its
        slot's index and every other row is dead (slot mapping -1, null
        block table, positions from 1 so that nothing resets: the step
        graph leaves a dead slot's tail and state as they were). Below it
        (the ``r_min``-row program of one prompt) the rows keep their
        order, padded by repeating row 0, and ``state_slots`` names each
        row's slot. Returns the packed tuple with ``state_slots`` and the
        output row of each sequence appended."""
        slot_of = np.asarray([self._state_slot[s] for s in sids], np.int32)
        if pad_to != self.app.state_slots:
            b = len(sids)
            if pad_to > b:
                seeds = _repeat_row0(seeds, pad_to)
                slot_of = _repeat_row0(slot_of, pad_to)
                if aids is not None:
                    aids = _repeat_row0(aids, pad_to)
            return _pad_paged_rows(pad_to, ids_w, pos_w, slots, bt, last) \
                + (seeds, aids, slot_of, None)
        width = ids_w.shape[1]

        def spread(x, fill=0):
            out = np.full((pad_to,) + x.shape[1:], fill, x.dtype)
            out[slot_of] = x
            return out

        dead_pos = 1 + np.arange(width, dtype=np.int32)
        pos_p = np.tile(dead_pos, (pad_to, 1))
        pos_p[slot_of] = pos_w
        return (spread(ids_w), pos_p, spread(slots, -1), spread(bt),
                spread(last, self._no_sample), spread(seeds),
                None if aids is None else spread(aids), None, slot_of)

    def _dispatch_prefill_chunk(self, packed, fetch: bool = True):
        """Issue ONE packed prefill-chunk dispatch without materializing
        any output (region lint: nxdi_lint host-sync pass) — the final-
        chunk token fetch happens in the caller, one async hop behind.
        ``fetch=False`` (intermediate-only dispatch) skips even the async
        device-to-host copy: those samples are never read."""
        (ids_p, pos_p, slots_p, bt_p, last_p, seeds_p, aids_p,
         state_slots, _) = packed
        kw = {"row_seeds": seeds_p}
        if aids_p is not None:
            kw["adapter_ids"] = aids_p
        if state_slots is not None:
            kw["state_slots"] = state_slots
        out = self.app._run_paged(ids_p, pos_p, slots_p, bt_p, last_p, **kw)
        if fetch:
            _async_fetch(out["tokens"])
        self.host_stats["prefill_dispatches"] += 1
        # which expert path THIS program took, read from the record its
        # trace left (the rule itself lives in moe.takes_ragged alone)
        notes = self.app.paged_program_notes(*ids_p.shape)
        experts = kernel_mode.experts_path(notes)
        if experts == "walk":
            self.host_stats["prefill_dispatches_moe_walk"] += 1
        attn = "xla"
        if kernel_mode.prefill_attn_on_kernel(notes):
            self.host_stats["prefill_dispatches_attn_kernel"] += 1
            attn = "latent"
        if kernel_mode.paged_prefill_on_kernel(notes):
            self.host_stats["prefill_dispatches_paged_attn_kernel"] += 1
            attn = "paged"
        if self._sparse is not None:
            self._count_select(ids_p.shape)
        self.telemetry.on_prefill_dispatch(experts, attn)
        return out

    def _fetch_prefill_tokens(self, out, waited: bool = True
                              ) -> Tuple[np.ndarray, float]:
        """Materialize a final-chunk dispatch's sampled tokens (the one
        blocking sync of a packed admission; async-prefetched). Returns
        them with the instant they became host-visible: the ``token`` stamp
        of the timelines whose first token is among them. ``waited`` False:
        a decode step enqueued behind the dispatch was fetched already, so
        nothing blocks and no blocking fetch is counted."""
        t0 = time.perf_counter()
        with _get_recorder().span("fetch.tokens", cat="adapter",
                                  engine=self.engine_name, phase="prefill"):
            toks = np.asarray(out["tokens"])
        t1 = time.perf_counter()
        if waited:
            self.host_stats["prefill_blocking_fetches"] += 1
            self.host_stats["prefill_blocked_s"] += t1 - t0
        return toks.reshape(toks.shape[0], -1), t1

    def _drop_unwritten(self, sid):
        """Retire ``sid``'s EXCLUSIVE blocks from the unwritten set. Any
        block another still-pending sequence shares stays: a shared prefix
        block keeps its registered hash while any holder references it,
        so its unwritten-ness must keep being tracked until the last
        pending holder confirms the write or tears down."""
        tbl = set(self.app.kv_mgr.tables.get(sid, ()))
        if not tbl:
            return
        for other in self._chunks:
            if other != sid:
                tbl.difference_update(self.app.kv_mgr.tables.get(other, ()))
        self._unwritten -= tbl

    def _abort_pending(self, sid):
        """Tear down one pending/rolled-back sequence's allocations: every
        block whose content never fully landed — the sequence's own
        unwritten tail AND prefix hits on another pending writer's
        still-unwritten blocks — is invalidated so the prefix cache can
        never serve it; fully-written blocks are freed as valid. The
        caller pops the ``_ChunkState`` first. Its state slot (recurrent
        stacks) goes back too: the next holder resets it by prefilling
        from position 0."""
        self._free_state_slot(sid)
        if sid not in self.app.kv_mgr.tables:
            return
        unwritten = set(self.app.kv_mgr.tables[sid]) & self._unwritten
        self._drop_unwritten(sid)
        self.app.kv_mgr.abort_sequence(sid, unwritten=unwritten)

    def _abort_prefill_rows(self, sids):
        """Transactional rollback of partially-prefilled sequences: drop
        their chunk state and abort their allocations — blocks whose
        content never fully landed are invalidated (they must not be
        served as prefix hits), fully-written blocks freed normally.
        REVERSE admission order, like :meth:`_rollback_admission`: the
        originating sequence's invalidate must be the last dereference of
        an intra-call shared-prefix hash."""
        for s in reversed(list(sids)):
            self._chunks.pop(s, None)
            self._abort_pending(s)
