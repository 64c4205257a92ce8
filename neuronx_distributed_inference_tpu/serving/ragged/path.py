"""The ragged unified engine step (see the package docstring).

:class:`RaggedDispatchPath` owns one engine step of the ragged mode of a
:class:`~..adapter.PagedEngineAdapter`:

  1. the :class:`~.planner.RaggedBatchPlanner` lays out ALL runnable work
     — live decode rows (width 1), speculative verify windows (width
     k+1, clamped like the standalone spec path) and pending prefill
     chunks (width n at each row's own suffix offset) — as ragged rows
     of ONE dispatch, padded to the unified
     ``autobucketing.ragged_row_buckets`` ladder;
  2. per-row KV growth for the live rows' candidate windows (preemption-
     aware, exactly like the non-ragged grow);
  3. with speculation attached, the proposer's draft pass (device-
     resident tokens merged into the packed input on device — drafts
     never round-trip through the host);
  4. THE ragged dispatch (``model_base.paged_ragged_step``): in-graph
     per-row sampling for decode rows and final prefill chunks, in-graph
     acceptance for verify windows — greedy exact-match, or gumbel-
     coupled rejection sampling under seeded sampled decode (README
     "Sampled speculation & compressed decode") — nothing emitted for
     intermediate chunks and pad rows;
  5. the ONE blocking fetch of the step, then host bookkeeping: chunk
     cursors advance (final chunks graduate to running rows),
     ``_unwritten`` blocks covered by the now-materialized write chain
     are confirmed, accept cursors advance and KV shrinks to each verify
     row's accepted prefix.

Failure contract: the ``ragged_step`` fault point fires between growth
and the dispatch; any dispatch/fetch failure rolls EVERY packed row back
to its last accepted/delivered token — live rows' KV growth shrunk,
positions untouched, prefill rows aborted exactly like a failed chunk
dispatch (never-written blocks cannot poison the prefix cache) — and
raises a typed :class:`~...resilience.errors.StepFailure` with
``phase="ragged"``. The dispatch helper (``_dispatch_ragged``) must never
materialize device values — tier-1 lint region (the ``host-sync`` pass
of ``scripts/nxdi_lint.py``); the single blocking sync per step is
:meth:`RaggedDispatchPath._fetch_ragged`.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...modules import autobucketing
from ...modules.block_kv_cache import slots_from_table
from ...resilience.errors import (CapacityError, ConfigurationError,
                                  ServingError, StepFailure)
from ...resilience.faults import FAULTS as _FAULTS
from ...telemetry.request_trace import trace_of as _trace_of
from ...telemetry.trace import get_recorder as _get_recorder
from ..adapter import (_async_fetch, _common_tenant, _live_rows,
                       _meta_seed, _meta_tenant, _pre_step_checks,
                       _repeat_row0, _trace_error)
from ..speculation.verifier import validate_spec_sampling
from .planner import (KIND_DECODE, KIND_PREFILL, KIND_VERIFY,
                      RaggedBatchPlanner, RaggedPlan)

__all__ = ["RaggedDispatchPath"]

logger = logging.getLogger("nxdi_tpu")

_EMIT_NONE, _EMIT_LAST, _EMIT_VERIFY = 0, 1, 2


class RaggedDispatchPath:
    """One mixed prefill+decode+verify dispatch per engine step."""

    def __init__(self, adapter, proposer=None):
        cfg = adapter.app.tpu_config
        if adapter._pos_limit is None:
            raise ConfigurationError(
                "the ragged unified dispatch over rolling-window caches "
                "is not supported (row offsets need absolute positions)")
        self.mode = validate_spec_sampling(cfg.on_device_sampling_config,
                                           where="ragged unified dispatch")
        self.adapter = adapter
        self.planner = RaggedBatchPlanner(adapter)
        # ONE warm-shape ladder for every row kind (decode / verify /
        # prefill chunk) — replaces the separate ctx-slice chunk ladder
        # and spec-width ladder, so mixed load never pays a second
        # warm-shape set
        self.row_buckets = autobucketing.ragged_row_buckets(
            adapter.app.ctx_buckets, adapter.prefill_chunk_tokens)
        self.proposer = proposer
        self.spec_path = None
        self.max_width = 1
        if proposer is not None:
            # reuse the speculative path's validation, proposer binding
            # and draft-dispatch lint regions wholesale — only its verify
            # dispatch is replaced by the unified one
            from ..speculation.verifier import SpeculativeDecodePath
            self.spec_path = SpeculativeDecodePath(adapter, proposer)
            self.max_width = min(self.spec_path.max_width,
                                 self.row_buckets[-1])
        stats = adapter.host_stats
        for key in ("ragged_steps", "ragged_dispatches",
                    "ragged_rows_decode", "ragged_rows_prefill",
                    "ragged_rows_verify", "ragged_pad_rows",
                    "ragged_real_tokens", "ragged_padded_tokens"):
            stats.setdefault(key, 0)

    @property
    def wants_hidden(self) -> bool:
        return self.proposer is not None and self.proposer.wants_hidden

    # -- the ragged engine step --------------------------------------------
    def step(self, seq_ids: Optional[Sequence[int]] = None,
             token_room: Optional[Dict[int, int]] = None
             ) -> Dict[int, List[int]]:
        """ONE unified engine step: every runnable row — decode, verify,
        prefill chunk — rides a single materialized dispatch. Returns
        ``{seq_id: [tokens]}`` (1..k+1 tokens per decode/verify row;
        first tokens of prompts whose final chunk landed this step).
        ``token_room`` (scheduler hook) clamps a verify row's candidate
        width so a step never overshoots its remaining token budget."""
        ad = self.adapter
        if ad._inflight is not None:
            ad._stash_flush()          # retire a pre-ragged pipelined step
        pending = ad._pending_ids()
        live = _live_rows(ad.seqs, seq_ids, pending)

        def drain() -> Dict[int, List[int]]:
            return {s: [t] for s, t in ad._drain_ready().items()}

        if not live and not pending:
            return drain()
        if _FAULTS.active:
            _FAULTS.fire("slow_step")
        if live:
            _pre_step_checks(ad.seqs, live, ad._pos_limit, ad.telemetry,
                             horizon=1)
        t0 = time.perf_counter()
        # degradation shed: verify windows clamp to width 1 (decode-kind
        # rows, no draft dispatch) — tokens unchanged in both modes
        # (greedy argmax trivially; coupled sampling because the
        # position-keyed draws are path-invariant)
        max_width = 1 if ad._spec_shed else self.max_width
        plan = self.planner.plan(live, seq_ids, token_room, max_width)
        if plan.live_ids:
            self._grow_plan(plan)
            plan.prune(ad)             # rows preempted mid-grow drop out
        if not plan.rows:
            return drain()
        # _ready (graduated first tokens) is drained only after the
        # fallible stages: a StepFailure mid-dispatch leaves them
        # deliverable by the next returning call instead of dropping them
        res = self._execute_plan(plan, t0)
        out = drain()
        for s, row in res.items():
            out.setdefault(s, []).extend(row)
        return out

    # -- internals ---------------------------------------------------------
    def _grow_plan(self, plan: RaggedPlan) -> None:
        """Grow every live row's block list to cover its candidate
        window, evicting victims per the adapter's preemption policy when
        the pool runs dry (rows preempted mid-grow leave the plan via
        :meth:`RaggedPlan.prune`). On an unevictable CapacityError all
        growth from this call is rolled back before the raise."""
        ad = self.adapter
        mgr = ad.app.kv_mgr
        widths = plan.widths
        queue = [s for s in plan.live_ids]
        grown: List[int] = []
        while queue:
            s = queue[0]
            if s not in ad.seqs:       # preempted by an earlier eviction
                queue.pop(0)
                continue
            try:
                mgr.grow(s, widths[s])
            except CapacityError:
                victim = ad._choose_victim()
                if victim is None:
                    for g in grown:
                        mgr.shrink(g, widths[g])
                    raise
                ad._preempt(victim, reason="grow")
                for lst in (queue, grown):
                    if victim in lst:
                        lst.remove(victim)
                continue
            queue.pop(0)
            grown.append(s)

    def _rollback_live(self, plan: RaggedPlan) -> None:
        """Shrink every live row's candidate-window growth back to its
        last accepted/delivered token (positions untouched — a retry
        continues the exact stream)."""
        ad = self.adapter
        for s in plan.live_ids:
            if s in ad.seqs and s in ad.app.kv_mgr.tables:
                ad.app.kv_mgr.shrink(s, plan.widths[s])

    def _rollback_plan(self, plan: RaggedPlan) -> None:
        """Dispatch-failure rollback: live rows shrink to their last
        accepted/delivered token, and every prefill row packed in the
        failed dispatch — its KV writes are suspect — is evicted as a
        PREEMPTION (reverse admission order — never-written blocks leave
        the prefix cache, and the :class:`Preempted` record lets the
        scheduler replay the admission instead of losing the request)."""
        self._rollback_live(plan)
        ad = self.adapter
        for s in reversed(plan.prefill_ids):
            if s in ad._chunks:
                ad._preempt(s, reason="ragged_rollback")

    def _draft(self, plan: RaggedPlan, live_rows) -> Tuple[Any, int, Any]:
        """Run the proposer's draft pass over the live (verify) rows
        through the speculative path's shared preamble
        (:meth:`~..speculation.verifier.SpeculativeDecodePath.run_draft`).
        Returns (drafts device array or None, bucketed spec width, ctx).
        A draft failure rolls back ONLY the live rows' window growth —
        the packed prefill rows saw no device work yet, so their pending
        state stays; a sat-out proposer releases the unused window."""
        import jax.numpy as jnp
        app = self.adapter.app
        live = [r.seq_id for r in live_rows]
        drafts, W, ctx = self.spec_path.run_draft(
            live, plan.widths, lambda: self._rollback_live(plan))
        if drafts is None and W > 1:
            # the proposer sat this step out: release the unused window
            for r in live_rows:
                if r.width > 1:
                    app.kv_mgr.shrink(r.seq_id, r.width - 1)
                    r.width = 1
                    plan.widths[r.seq_id] = 1
            W = 1
            ctx.num_drafts = 0
            ctx.widths = np.ones_like(ctx.widths)
        if drafts is not None:
            drafts = jnp.asarray(drafts)
        return drafts, W, ctx

    def _execute_plan(self, plan: RaggedPlan,
                      t0: float) -> Dict[int, List[int]]:
        import jax.numpy as jnp
        ad = self.adapter
        app = ad.app
        chunks = ad._chunks
        rows = plan.rows
        live_rows = [(i, r) for i, r in enumerate(rows)
                     if r.kind != KIND_PREFILL]
        # draft BEFORE packing: verify widths may degrade to 1 when the
        # proposer sits the step out. The ctx is built even for a fully
        # clamped (width-1) batch so feature-feeding proposers
        # (Medusa/EAGLE) keep seeding from the verify hidden states,
        # exactly like the standalone speculative path
        drafts, spec_W, ctx = (None, 1, None)
        if self.spec_path is not None and live_rows:
            drafts, spec_W, ctx = self._draft(plan,
                                              [r for _, r in live_rows])
        prefill_rows = [(i, r) for i, r in enumerate(rows)
                        if r.kind == KIND_PREFILL]
        b = len(rows)
        W = autobucketing.get_target_bucket(
            self.row_buckets, max(r.width for r in rows), kind="ragged")
        pad_to = autobucketing.get_target_bucket(app.batch_buckets, b,
                                                 kind="batch")
        sids = [r.seq_id for r in rows]
        bs = app.kv_mgr.spec.block_size
        bt = app.kv_mgr.block_table_array(sids, app._bt_width_for(sids))
        ids = np.zeros((b, W), np.int32)
        pos = np.zeros((b, W), np.int32)
        slot_pos = np.full((b, W), -1, np.int32)
        wid = np.zeros((b,), np.int32)
        emit = np.zeros((b,), np.int32)
        cols = np.arange(W, dtype=np.int32)
        for i, r in enumerate(rows):
            wid[i] = r.width
            pos[i] = r.offset + cols
            slot_pos[i, :r.width] = pos[i, :r.width]
            if r.kind == KIND_PREFILL:
                st = chunks[r.seq_id]
                ids[i, :r.width] = st.prompt[r.offset:r.offset + r.width]
                emit[i] = _EMIT_LAST if r.final else _EMIT_NONE
            else:
                ids[i, 0] = ad.seqs[r.seq_id].last_token
                emit[i] = (_EMIT_VERIFY if r.kind == KIND_VERIFY
                           else _EMIT_LAST)
        slots = slots_from_table(bt, slot_pos, bs)
        seeds = np.asarray(
            [_meta_seed(ad.seqs[r.seq_id].meta if r.seq_id in ad.seqs
                        else chunks[r.seq_id].meta) for r in rows],
            np.int32)
        # per-row LoRA slots ride the plan (RaggedRow.adapter_id, pinned
        # at admission): ONE dispatch mixes rows from different adapters;
        # -1 (base model) clamps to slot 0, the zero adapter. None
        # without a pool — the kwarg is never passed, so no-pool graphs
        # stay byte-identical
        aids = None
        if ad._lora_pool is not None:
            aids = np.asarray([max(r.adapter_id, 0) for r in rows],
                              np.int32)
        if pad_to > b:
            ids, pos, slots, bt, wid, emit, seeds = (
                _repeat_row0(x, pad_to)
                for x in (ids, pos, slots, bt, wid, emit, seeds))
            if aids is not None:
                aids = _repeat_row0(aids, pad_to)
        ids_dev = jnp.asarray(ids)
        if drafts is not None and spec_W > 1:
            # merge the device-resident drafts into the packed input —
            # verify rows are the plan's live prefix, candidates never
            # round-trip through the host
            n_live = len(live_rows)
            ids_dev = ids_dev.at[:n_live, 1:spec_W].set(
                drafts[:n_live, :spec_W - 1])
            if pad_to > b:
                # batch-pad rows are clones of row 0 (a verify row when
                # any live row exists) and share its slot mapping — they
                # must carry row 0's DRAFTS too, or their duplicate KV
                # writes would race row 0's with different values
                ids_dev = ids_dev.at[b:, 1:spec_W].set(
                    drafts[0, :spec_W - 1][None])
        if ctx is not None:
            # ctx.cand must honor the spec-context row contract (live
            # rows then ROW-0 CLONES): the ragged grid's rows past the
            # live prefix are prefill/pad rows, so re-pad by gather —
            # EAGLE's draft-cache refresh scatters cand at row-0-cloned
            # positions and duplicate writes must stay value-identical
            n_live = len(live_rows)
            gather = np.concatenate(
                [np.arange(n_live, dtype=np.intp),
                 np.zeros(ctx.padded_batch - n_live, dtype=np.intp)])
            ctx.cand = ids_dev[jnp.asarray(gather), :spec_W]
        # per-tenant failure attribution covers EVERY packed row —
        # pending prefill rows carry their meta in the chunk state
        tenant = _common_tenant(
            [_meta_tenant(ad.seqs[s].meta) for s in sids if s in ad.seqs]
            + [_meta_tenant(chunks[s].meta) for s in sids if s in chunks])
        cache_before = app.cache
        try:
            if _FAULTS.active:
                _FAULTS.fire("ragged_step")
            out = self._dispatch_ragged(ids_dev, pos, slots, bt, wid,
                                        emit, seeds, rows, aids)
            toks, n_emit = self._fetch_ragged(out, b)
        except ServingError as e:
            self._rollback_plan(plan)
            _trace_error(e)
            raise
        except Exception as e:
            self._rollback_plan(plan)
            ad.telemetry.on_step_failure("ragged", tenant)
            raise _trace_error(StepFailure(
                "ragged unified dispatch failed; every packed row was "
                "rolled back to its last accepted/delivered token",
                phase="ragged", seq_ids=tuple(sids),
                retry_safe=app.cache is cache_before)) from e
        return self._accept(plan, live_rows, prefill_rows, toks, n_emit,
                            out, ctx, spec_W, t0, b, W, pad_to)

    def _accept(self, plan, live_rows, prefill_rows, toks, n_emit, out,
                ctx, spec_W, t0, b, W, pad_to) -> Dict[int, List[int]]:
        """Post-fetch host bookkeeping (the dispatch is materialized)."""
        import jax.numpy as jnp
        ad = self.adapter
        app = ad.app
        chunks = ad._chunks
        bs = app.kv_mgr.spec.block_size
        # 1. chunk cursors advance; the fetch above materialized the
        # dispatch, so every block the donated-cache chain covers up to
        # each pending row's cursor is now confirmed written
        t_token = (time.perf_counter()
                   if any(r.final for _, r in prefill_rows) else None)
        for _, r in prefill_rows:
            chunks[r.seq_id].done += r.width
            # the timeline's adapter stamps (telemetry/request_trace.py):
            # a prompt's first chunk went out with the step that began at
            # t0, its first token is host-visible since the fetch above
            chunks[r.seq_id].timeline.stamp("dispatch", t0)
        for s2, cst in chunks.items():
            ad._unwritten.difference_update(
                app.kv_mgr.tables[s2][:cst.done // bs])
        # 2. final chunks graduate to running rows
        from ..adapter import _SeqState, _meta_tenant
        for i, r in prefill_rows:
            if not r.final:
                continue
            st = chunks.pop(r.seq_id)
            ad._unwritten.difference_update(app.kv_mgr.tables[r.seq_id])
            tok = int(toks[i, 0])
            ad.seqs[r.seq_id] = _SeqState(
                position=len(st.prompt), last_token=tok,
                tokens=list(st.prompt) + [tok],
                prompt_len=len(st.prompt), admit_idx=st.admit_idx,
                deadline=st.deadline, meta=st.meta)
            ad._scratch = None         # live set grew
            ad._ready[r.seq_id] = tok
            if st.timeline.stamp("token", t_token):
                _get_recorder().mark("request.token", _trace_of(st.meta))
            ad.telemetry.on_add([r.seq_id], [st.prompt], st.t0,
                                [t_token], live=1,
                                padded=1, count_rows=False,
                                tenants=[_meta_tenant(st.meta)])
        # 3. live rows: accept cursors advance, KV shrinks to the
        # accepted prefix
        res: Dict[int, List[int]] = {}
        drafted = accepted = 0
        spec_rows = []
        for i, r in live_rows:
            st = ad.seqs[r.seq_id]
            n = int(n_emit[i])
            row = [int(t) for t in toks[i, :n]]
            st.position += n
            for t in row:
                ad._append_token(st, t)
            if r.width > n:
                app.kv_mgr.shrink(r.seq_id, r.width - n)
            res[r.seq_id] = row
            drafted += r.width - 1
            accepted += n - 1
            spec_rows.append((r.seq_id, n))
        # 4. telemetry + always-on host counters
        stats = ad.host_stats
        n_decode = sum(1 for _, r in live_rows if r.kind == KIND_DECODE)
        n_verify = len(live_rows) - n_decode
        real = sum(r.width for r in plan.rows)
        stats["ragged_steps"] += 1
        stats["ragged_rows_decode"] += n_decode
        stats["ragged_rows_verify"] += n_verify
        stats["ragged_rows_prefill"] += len(prefill_rows)
        stats["ragged_pad_rows"] += pad_to - b
        stats["ragged_real_tokens"] += real
        stats["ragged_padded_tokens"] += pad_to * W
        if prefill_rows:
            pre_real = sum(r.width for _, r in prefill_rows)
            stats["prefill_real_tokens"] += pre_real
            stats["prefill_padded_tokens"] += len(prefill_rows) * W
            ad.telemetry.on_prefill_chunk(len(prefill_rows),
                                          len(prefill_rows), pre_real,
                                          len(prefill_rows) * W)
        ad.telemetry.on_ragged_step(
            {KIND_DECODE: n_decode, KIND_VERIFY: n_verify,
             KIND_PREFILL: len(prefill_rows), "pad": pad_to - b},
            real, pad_to * W)
        if self.spec_path is not None and spec_rows:
            stats["spec_steps"] += 1
            stats["spec_drafted_tokens"] += drafted
            stats["spec_accepted_tokens"] += accepted
            ad.telemetry.on_spec_step(spec_rows, t0, padded=pad_to,
                                      width=spec_W, drafted=drafted,
                                      accepted=accepted, mode=self.mode)
        elif spec_rows:
            ad.telemetry.on_step([s for s, _ in spec_rows], t0,
                                 padded=pad_to)
        # 5. proposer feedback (Medusa/EAGLE): hand back the ctx-shaped
        # slice of the unified dispatch's outputs — ctx pad rows must be
        # row-0 clones, so the live prefix is re-padded by gather
        if ctx is not None:
            n_live = len(live_rows)
            hidden = None
            if self.wants_hidden:
                gather = np.concatenate(
                    [np.arange(n_live, dtype=np.intp),
                     np.zeros(ctx.padded_batch - n_live, dtype=np.intp)])
                hidden = out["hidden"][jnp.asarray(gather), :spec_W, :]
            try:
                self.proposer.on_verify(ctx, toks[:n_live, :spec_W],
                                        n_emit[:n_live], hidden)
            except Exception:
                # the step's tokens are already accepted and delivered —
                # a broken proposer must only cost acceptance rate, never
                # the output stream
                logger.warning(
                    "speculative proposer %r failed in on_verify; its "
                    "per-sequence state was dropped (seq_ids=%s)",
                    self.proposer.name, list(ctx.live), exc_info=True)
                self.proposer.forget(ctx.live)
        return res

    # -- dispatch region (nxdi_lint host-sync pass) ------------------------
    def _dispatch_ragged(self, ids_dev, pos, slots, bt, wid, emit, seeds,
                         rows, aids=None):
        """Issue THE unified dispatch (one per engine step) without
        materializing any output; the async copies are started so the
        fetch one call later is cheap."""
        ad = self.adapter
        kw = {"want_hidden": self.wants_hidden, "row_seeds": seeds}
        if aids is not None:
            kw["adapter_ids"] = aids
        if ad.app._steady_state:
            # steady-state compile discipline (serving/warmup.py): carry
            # the packed rows' request trace ids so an unexpected
            # recompile is attributed to its victims' trace lanes
            with ad.app.request_context(
                    self._row_trace(r.seq_id) for r in rows):
                out = ad.app._run_ragged(ids_dev, pos, slots, bt, wid,
                                         emit, **kw)
        else:
            out = ad.app._run_ragged(ids_dev, pos, slots, bt, wid, emit,
                                     **kw)
        _async_fetch(out["tokens"])
        _async_fetch(out["num_emitted"])
        ad.host_stats["dispatches"] += 1
        ad.host_stats["ragged_dispatches"] += 1
        ad.host_stats["device_steps"] += 1
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("dispatch.ragged", cat="adapter",
                        engine=ad.engine_name, rows=len(rows),
                        pad_to=int(wid.shape[0]),
                        width=int(ids_dev.shape[1]),
                        kinds={r.kind: sum(1 for x in rows
                                           if x.kind == r.kind)
                               for r in rows},
                        seq_ids=[int(r.seq_id) for r in rows],
                        # per-row request trace ids (aligned with
                        # seq_ids), so a request's trace lane shows
                        # every ragged dispatch it occupied a row of
                        traces=[self._row_trace(r.seq_id) for r in rows])
        return out

    def _row_trace(self, seq_id: int):
        """The request trace id behind one packed row — live rows carry
        meta on their _SeqState, pending prefill rows on their chunk
        state. Recorder-enabled path only (never called while tracing
        is off)."""
        ad = self.adapter
        st = ad.seqs.get(seq_id)
        meta = st.meta if st is not None else getattr(
            ad._chunks.get(seq_id), "meta", None)
        return _trace_of(meta)

    def _fetch_ragged(self, out, b: int):
        """The ONE blocking sync of a ragged engine step."""
        ad = self.adapter
        t0 = time.perf_counter()
        with _get_recorder().span("fetch.tokens", cat="adapter",
                                  engine=ad.engine_name, rows=b,
                                  phase="ragged"):
            toks = np.asarray(out["tokens"])[:b]
            n_emit = np.asarray(out["num_emitted"])[:b]
        ad.host_stats["blocking_fetches"] += 1
        ad.host_stats["blocked_s"] += time.perf_counter() - t0
        return toks, n_emit
