"""The speculative engine-step machinery (see package docstring).

:class:`SpeculativeDecodePath` owns one engine step of draft-and-verify
decode on a :class:`~..adapter.PagedEngineAdapter`:

  1. per-row candidate widths — ``k+1`` clamped by seq_len headroom and
     the scheduler's per-row token room — padded to the
     ``autobucketing.spec_width_buckets`` ladder (a fully clamped batch
     degenerates to an eager-equivalent width-1 verify);
  2. per-row KV growth for the whole candidate window (preemption-aware:
     pool pressure evicts victims exactly like the non-speculative grow);
  3. the proposer's draft pass (device-resident tokens — drafts never
     round-trip through the host, in eager AND pipelined modes);
  4. ONE batched k+1-token verify dispatch over the existing
     block-table/slot-mapping graph with in-graph acceptance
     (``model_base.paged_spec_verify``) — greedy exact-match, or
     gumbel-coupled rejection sampling when the adapter runs seeded
     sampled decode (README "Sampled speculation & compressed decode") —
     columns past a row's width at slot -1 (dropped writes);
  5. host accept bookkeeping: per-sequence accept cursors advance
     ``_SeqState.position``/``tokens`` by ``num_emitted``, KV shrinks to
     the accepted prefix (``BlockKVCacheManager.shrink``), and the step
     returns variable tokens-per-row ``{seq_id: [tokens]}``.

Failure contract: ``spec_draft``/``spec_verify`` fault points fire at the
two dispatches; any failure shrinks every packed row's KV growth back to
its last ACCEPTED token and leaves positions untouched, then raises a
typed :class:`~...resilience.errors.StepFailure` — no half-accepted cache
poisoning (pinned by tests/test_spec_serving.py). The dispatch helpers
(``_dispatch_spec_draft`` / ``_dispatch_spec_verify``) must never
materialize device values — tier-1 lint region
(the ``host-sync`` pass of ``scripts/nxdi_lint.py``); the single
blocking sync per step is
the verify fetch.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...modules import autobucketing
from ...modules.block_kv_cache import slots_from_table
from ...resilience.errors import (CapacityError, ConfigurationError,
                                  ServingError, StepFailure)
from ...resilience.faults import FAULTS as _FAULTS
from ...telemetry.trace import get_recorder as _get_recorder
from ..adapter import (_async_fetch, _live_rows, _meta_seed,
                       _pre_step_checks, _repeat_row0, _trace_error)
from .proposer import DraftProposer

__all__ = ["SpeculativeDecodePath", "validate_spec_sampling"]

logger = logging.getLogger("nxdi_tpu")


def validate_spec_sampling(sampling_config, where: str) -> str:
    """Resolve a speculative path's verify mode from the adapter's
    on-device sampling config: ``"greedy"`` (no config, or
    ``do_sample=False``) or ``"sampled"`` (seeded coupled sampling —
    ``do_sample=True`` with ``stream_seed`` set). UNSEEDED sampling is
    the one still-refused configuration: without a stream seed every
    dispatch draws fresh noise, so verify could never reproduce the
    target draw a draft must match and the emitted stream would depend
    on batch composition."""
    if sampling_config is None or not sampling_config.do_sample:
        return "greedy"
    if sampling_config.stream_seed is None:
        raise ConfigurationError(
            f"{where} supports sampled speculation only for SEEDED "
            "streams: set on_device_sampling_config.stream_seed (coupled "
            "rejection sampling replays the per-position gumbel draw the "
            "draft must match). Supported: greedy (do_sample=False, no "
            "seed needed) and seeded sampling; unseeded do_sample is "
            "not.")
    return "sampled"


@dataclass
class _SpecContext:
    """Everything a proposer needs to draft for one engine step. Arrays
    are already padded to the batch bucket (pad rows clone row 0 — the
    usual invariant); ``cand`` is filled in before ``on_verify`` so
    feature-refreshing proposers (EAGLE) see the verified candidates."""
    path: "SpeculativeDecodePath"
    live: Tuple[int, ...]          # live seq_ids, dispatch row order
    b: int                         # live rows (before batch padding)
    padded_batch: int
    num_drafts: int                # bucketed width - 1
    first: np.ndarray              # (Bp,) last accepted tokens
    positions: np.ndarray          # (Bp,) their positions
    widths: np.ndarray             # (Bp,) per-row candidate widths
    block_table: np.ndarray        # (Bp, table-width bucket)
    seeds: np.ndarray = None       # (Bp,) per-row sampling stream seeds
    aids: np.ndarray = None        # (Bp,) per-row LoRA adapter slots
    cand: Any = field(default=None)  # (Bp, W) device candidates


class SpeculativeDecodePath:
    """Draft-and-verify stepping for one paged adapter + one proposer."""

    def __init__(self, adapter, proposer: DraftProposer):
        if not isinstance(proposer, DraftProposer):
            raise ConfigurationError(
                "speculation= takes a DraftProposer (e.g. "
                f"SelfDraftProposer(k)), got {type(proposer).__name__}")
        cfg = adapter.app.tpu_config
        if adapter._pos_limit is None:
            raise ConfigurationError(
                "speculative decode over rolling-window caches is not "
                "supported (the accept window needs absolute positions)")
        self.mode = validate_spec_sampling(cfg.on_device_sampling_config,
                                           where="speculative serving")
        self.adapter = adapter
        self.proposer = proposer
        self.max_width = proposer.max_drafts + 1
        self.width_buckets = autobucketing.spec_width_buckets(self.max_width)
        stats = adapter.host_stats
        for key in ("spec_steps", "spec_draft_dispatches",
                    "spec_verify_dispatches", "spec_drafted_tokens",
                    "spec_accepted_tokens"):
            stats.setdefault(key, 0)
        proposer.bind(adapter)

    # -- the speculative engine step ---------------------------------------
    def step(self, seq_ids: Optional[Sequence[int]] = None,
             token_room: Optional[Dict[int, int]] = None
             ) -> Dict[int, List[int]]:
        """One speculative engine step: at most one prefill-chunk
        dispatch (mixed load), one draft pass and EXACTLY one verify
        dispatch; returns ``{seq_id: [accepted tokens + bonus]}`` with
        1..k+1 tokens per row. ``token_room`` (scheduler hook) clamps a
        row's candidate width so a step never overshoots its remaining
        token budget."""
        ad = self.adapter
        if ad._inflight is not None:
            ad._stash_flush()          # retire a pre-spec pipelined step
        pending = ad._pending_ids()
        live = _live_rows(ad.seqs, seq_ids, pending)

        def drain() -> Dict[int, List[int]]:
            return {s: [t] for s, t in ad._drain_ready().items()}

        if not live and not pending:
            return drain()
        if _FAULTS.active:
            _FAULTS.fire("slow_step")
        if live:
            # deadlines + the 1-token floor BEFORE any draft work; the
            # spec window itself is clamped per row, never raised on
            _pre_step_checks(ad.seqs, live, ad._pos_limit, ad.telemetry,
                             horizon=1)
        ad._advance_prefill(seq_ids)
        live = ad._rows_after_chain(live, seq_ids)
        if not live:
            return drain()
        t0 = time.perf_counter()
        limit = ad._pos_limit
        # degradation shed: every window clamps to width 1 — the step
        # degenerates to the eager-equivalent verify (no draft dispatch,
        # same tokens in both modes: greedy argmax trivially, coupled
        # sampling because the position-keyed draws are path-invariant);
        # see PagedEngineAdapter.set_speculation_shed
        max_w = 1 if ad._spec_shed else self.max_width
        widths = {}
        for s in live:
            w = min(max_w, limit - ad.seqs[s].position)
            if token_room is not None and s in token_room:
                w = min(w, token_room[s])
            widths[s] = max(1, int(w))
        live = self._grow_for_spec(live, widths)
        if not live:
            return drain()
        # _ready (first tokens from finished prefills) is drained only
        # after the fallible stages: a StepFailure mid-verify leaves them
        # deliverable by the next returning call instead of dropping them
        res = self._draft_verify_accept(live, widths, t0)
        out = drain()
        for s, row in res.items():
            out.setdefault(s, []).extend(row)
        return out

    # -- internals ---------------------------------------------------------
    def _grow_for_spec(self, live: List[int],
                       widths: Dict[int, int]) -> List[int]:
        """Grow every row's block list to cover its candidate window,
        evicting victims per the adapter's preemption policy when the
        pool runs dry (rows preempted mid-grow leave ``live``). On an
        unevictable CapacityError all growth from this call is rolled
        back before the raise."""
        ad = self.adapter
        mgr = ad.app.kv_mgr
        live = list(live)
        queue = list(live)
        grown: List[int] = []
        while queue:
            s = queue[0]
            try:
                mgr.grow(s, widths[s])
            except CapacityError:
                victim = ad._choose_victim()
                if victim is None:
                    for g in grown:
                        mgr.shrink(g, widths[g])
                    raise
                ad._preempt(victim, reason="grow")
                for lst in (queue, live, grown):
                    if victim in lst:
                        lst.remove(victim)
                continue
            queue.pop(0)
            grown.append(s)
        return live

    def _rollback(self, live: Sequence[int], widths: Dict[int, int]):
        for s in live:
            self.adapter.app.kv_mgr.shrink(s, widths[s])

    def run_draft(self, live: List[int], widths: Dict[int, int],
                  rollback) -> Tuple[Any, int, _SpecContext]:
        """The draft preamble shared by the standalone speculative step
        and the ragged unified step: build the row-0-padded
        :class:`_SpecContext` over ``live``, fire the ``spec_draft``
        fault point, and run the proposer's draft pass. On any failure
        ``rollback()`` unwinds the caller's KV growth before the typed
        raise. Returns ``(drafts or None, bucketed width W, ctx)`` —
        a sat-out proposer (``drafts is None`` with ``W > 1``) leaves
        the unused-window release to the caller."""
        ad = self.adapter
        app = ad.app
        b = len(live)
        pad_to = autobucketing.get_target_bucket(app.batch_buckets, b,
                                                 kind="batch")
        wmax = max(widths[s] for s in live)
        W = autobucketing.get_target_bucket(self.width_buckets, wmax,
                                            kind="spec")
        first = np.asarray([ad.seqs[s].last_token for s in live], np.int32)
        pos = np.asarray([ad.seqs[s].position for s in live], np.int32)
        wid = np.asarray([widths[s] for s in live], np.int32)
        seeds = np.asarray([_meta_seed(ad.seqs[s].meta) for s in live],
                           np.int32)
        aids = ad._lora_aids(live)
        if aids is not None:
            aids = np.asarray(aids, np.int32)
        bt = app.kv_mgr.block_table_array(live, app._bt_width_for(live))
        if pad_to > b:
            first, pos, wid, seeds, bt = (_repeat_row0(x, pad_to)
                                          for x in (first, pos, wid,
                                                    seeds, bt))
            if aids is not None:
                aids = _repeat_row0(aids, pad_to)
        ctx = _SpecContext(path=self, live=tuple(live), b=b,
                           padded_batch=pad_to, num_drafts=W - 1,
                           first=first, positions=pos, widths=wid,
                           block_table=bt, seeds=seeds, aids=aids)
        cache_before = app.cache
        try:
            if _FAULTS.active:
                _FAULTS.fire("spec_draft")
            if app._steady_state:
                # a draft-pass compile in steady state is an incident like
                # any other: attribute it to the live rows' request traces
                with app.request_context(ad._traces_of(live)):
                    drafts = (self.proposer.propose(ctx) if W > 1 else None)
            else:
                drafts = (self.proposer.propose(ctx) if W > 1 else None)
        except ServingError as e:
            rollback()
            _trace_error(e)
            raise
        except Exception as e:
            rollback()
            ad.telemetry.on_step_failure("spec", ad._tenant_of(live))
            raise _trace_error(StepFailure(
                "speculative draft pass failed; KV growth was rolled back "
                "and positions were not advanced",
                phase="spec_draft", seq_ids=tuple(live),
                retry_safe=app.cache is cache_before)) from e
        return drafts, W, ctx

    def _draft_verify_accept(self, live: List[int], widths: Dict[int, int],
                             t0: float) -> Dict[int, List[int]]:
        import jax.numpy as jnp
        ad = self.adapter
        app = ad.app
        tenant = ad._tenant_of(live)
        drafts, W, ctx = self.run_draft(
            live, widths, lambda: self._rollback(live, widths))
        b, pad_to = ctx.b, ctx.padded_batch
        first, pos, wid, bt = (ctx.first, ctx.positions, ctx.widths,
                               ctx.block_table)
        if drafts is None and W > 1:
            # the proposer sat this step out: release the unused window
            for s in live:
                if widths[s] > 1:
                    app.kv_mgr.shrink(s, widths[s] - 1)
                    widths[s] = 1
            wid = np.ones_like(wid)
            W = 1
            ctx.num_drafts = 0
            ctx.widths = wid
        first_dev = jnp.asarray(first)[:, None]
        cand = (first_dev if W == 1 else
                jnp.concatenate([first_dev, jnp.asarray(drafts)[:, :W - 1]],
                                axis=1))
        ctx.cand = cand
        cols = np.arange(W, dtype=np.int32)[None, :]
        pos_w = pos[:, None] + cols
        slot_pos = np.where(cols < wid[:, None], pos_w, -1)
        slots = slots_from_table(bt, slot_pos, app.kv_mgr.spec.block_size)
        # re-snapshot AFTER the draft: stale draft KV past the accepted
        # prefix is rewritten before any read, so a failure in front of
        # the verify dispatch leaves a retryable cache — only a crash
        # inside the dispatch itself (donated buffers consumed) is not
        cache_before = app.cache
        try:
            if _FAULTS.active:
                _FAULTS.fire("spec_verify")
            out = self._dispatch_spec_verify(ctx, cand, pos_w, slots)
            toks, n_emit = self._fetch_verify(out, b)
        except ServingError as e:
            self._rollback(live, widths)
            _trace_error(e)
            raise
        except Exception as e:
            self._rollback(live, widths)
            ad.telemetry.on_step_failure("spec", tenant)
            raise _trace_error(StepFailure(
                "speculative verify dispatch failed; every packed row was "
                "rolled back to its last accepted token",
                phase="spec_verify", seq_ids=tuple(live),
                retry_safe=app.cache is cache_before)) from e
        res: Dict[int, List[int]] = {}
        drafted = accepted = delivered = 0
        rows = []
        for i, s in enumerate(live):
            st = ad.seqs[s]
            w = widths[s]
            n = int(n_emit[i])
            row = [int(t) for t in toks[i, :n]]
            st.position += n
            for t in row:
                ad._append_token(st, t)
            if w > n:
                app.kv_mgr.shrink(s, w - n)
            res[s] = row
            drafted += w - 1
            accepted += n - 1
            delivered += n
            rows.append((s, n))
        stats = ad.host_stats
        stats["spec_steps"] += 1
        stats["spec_drafted_tokens"] += drafted
        stats["spec_accepted_tokens"] += accepted
        ad.telemetry.on_spec_step(rows, t0, padded=pad_to, width=W,
                                  drafted=drafted, accepted=accepted,
                                  mode=self.mode)
        try:
            self.proposer.on_verify(ctx, toks, n_emit,
                                    out.get("hidden")
                                    if self.proposer.wants_hidden else None)
        except Exception:
            # the step's tokens are already accepted and delivered — a
            # broken proposer must only cost acceptance rate, never the
            # output stream: drop its per-sequence state and keep serving
            logger.warning(
                "speculative proposer %r failed in on_verify; its "
                "per-sequence state was dropped (seq_ids=%s)",
                self.proposer.name, list(live), exc_info=True)
            self.proposer.forget(live)
        return res

    # -- dispatch regions (nxdi_lint host-sync pass) -----------------------
    def _dispatch_spec_draft(self, ctx: _SpecContext):
        """Issue the self-draft loop WITHOUT materializing any output —
        the draft tokens stay on device and feed the verify dispatch
        directly (in eager and pipelined modes alike)."""
        ad = self.adapter
        kw = {"row_seeds": ctx.seeds}
        if ctx.aids is not None:
            kw["adapter_ids"] = ctx.aids
        out = ad.app._run_spec_draft(ctx.first, ctx.positions,
                                     ctx.block_table, ctx.widths,
                                     ctx.num_drafts, **kw)
        ad.host_stats["dispatches"] += 1
        ad.host_stats["spec_draft_dispatches"] += 1
        ad.host_stats["device_steps"] += ctx.num_drafts
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("dispatch.spec_draft", cat="adapter",
                        engine=ad.engine_name, rows=ctx.b,
                        pad_to=ctx.padded_batch, drafts=ctx.num_drafts,
                        seq_ids=list(ctx.live))
        return out["tokens"]

    def _dispatch_propose(self, proposer, ctx: _SpecContext):
        """Proposer-side draft dispatch (Medusa heads / EAGLE chain):
        device work only, tokens stay on device."""
        ad = self.adapter
        toks = proposer._propose_device(ctx)
        ad.host_stats["dispatches"] += 1
        ad.host_stats["spec_draft_dispatches"] += 1
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("dispatch.spec_draft", cat="adapter",
                        engine=ad.engine_name, rows=ctx.b,
                        pad_to=ctx.padded_batch, drafts=ctx.num_drafts,
                        proposer=proposer.name, seq_ids=list(ctx.live))
        return toks

    def _dispatch_eagle_refresh(self, proposer, ctx: _SpecContext, hidden):
        """EAGLE draft-cache refresh dispatch (verified pairs)."""
        ad = self.adapter
        proposer._refresh_device(ctx, hidden)
        ad.host_stats["dispatches"] += 1
        ad.host_stats["spec_draft_dispatches"] += 1

    def _dispatch_spec_verify(self, ctx: _SpecContext, cand, pos_w, slots):
        """Issue THE verify dispatch (one per engine step) without
        materializing any output; the async copies are started so the
        fetch one call later is cheap."""
        ad = self.adapter
        kw = {"row_seeds": ctx.seeds}
        if ctx.aids is not None:
            kw["adapter_ids"] = ctx.aids
        out = ad.app._run_spec_verify(
            cand, pos_w, slots, ctx.block_table, ctx.widths,
            want_hidden=self.proposer.wants_hidden, **kw)
        _async_fetch(out["tokens"])
        _async_fetch(out["num_emitted"])
        ad.host_stats["dispatches"] += 1
        ad.host_stats["spec_verify_dispatches"] += 1
        ad.host_stats["device_steps"] += 1
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("dispatch.spec_verify", cat="adapter",
                        engine=ad.engine_name, rows=ctx.b,
                        pad_to=ctx.padded_batch, width=int(cand.shape[1]),
                        seq_ids=list(ctx.live))
        return out

    def _fetch_verify(self, out, b: int):
        """The ONE blocking sync of a speculative step."""
        ad = self.adapter
        t0 = time.perf_counter()
        with _get_recorder().span("fetch.tokens", cat="adapter",
                                  engine=ad.engine_name, rows=b,
                                  phase="spec"):
            toks = np.asarray(out["tokens"])[:b]
            n_emit = np.asarray(out["num_emitted"])[:b]
        ad.host_stats["blocking_fetches"] += 1
        ad.host_stats["blocked_s"] += time.perf_counter() - t0
        return toks, n_emit
