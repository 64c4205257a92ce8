"""The serving engine keeps one decode step in flight (ISSUE 35).

``ServingEngine(PagedEngineAdapter(app))``, built with no arguments, drives
the adapter through ``step_ahead()``: a pass enqueues step N+1 before it
blocks on step N's tokens, so the host's pass runs while the device
computes. Everything here compares that default stack with the SAME stack
built with ``pipeline_depth=0`` (eager under the engine), pass by pass on
the CPU in float32, greedy:

  (a) token streams, counts and finish reasons are equal, across
      admissions and finishes mid-run, a stop token, ``max_new_tokens`` 1
      and 2, a cancel while a step is in flight and a slow consumer under
      ``max_unread_tokens`` — for a plain attention stack, the granite toy
      stack (Mamba-2 mixers) and the delta-rule toy stack (rows in slot
      order);
  (b) ``host_stats`` shows the engagement: ``overlapped_dispatches``, a
      row that joined or left CARRIED under what caused it (ISSUE 62: no
      admission and no finish drains the step in flight, with or without
      ``prefill_budget_tokens``, under which a prompt's last chunk is not
      waited for either) and each drain that is left under its cause;
  (c) nothing but warmed step programs is dispatched after
      ``declare_steady_state()``, and a step fed on the device is the
      executable a step fed from the host is;
  (d) a deferred fetch failure (``pipeline_flush``) fails every stream with
      the ``StepFailure``, loses and repeats no delivered token, and runs
      the fatal teardown once; a row at the compiled ``seq_len`` gets its
      last token before it ends;
  (e) ISSUE 63: under the engine a default adapter's admission runs no
      chunk; prompts of 1, 3 and 9 chunks admitted while rows decode are
      paced between the decode steps (``k`` dispatches a pass, read from
      the observed prefill load; a last chunk parked) and every stream is
      the row served alone, on the attention, the recurrent and the
      expert stacks; a chunk fault sends its prompts back to the queue; a
      budgeted adapter issues the dispatches it always issued.
"""

import asyncio
import collections
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark"), os.path.join(ROOT, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_olmo_hybrid_paged as delta_toy  # noqa: E402
import test_recurrent_paged as granite_toy  # noqa: E402
from harness import build, weights  # noqa: E402

from neuronx_distributed_inference_tpu import telemetry  # noqa: E402
from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.llama import (  # noqa: E402
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.resilience import (  # noqa: E402
    FAULTS, StepFailure)
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.engine import \
    ServingEngine  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import \
    precompile  # noqa: E402
from neuronx_distributed_inference_tpu.telemetry import \
    metrics as tmetrics  # noqa: E402

LLAMA = dict(model_type="llama", hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=128,
             rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
             tie_word_embeddings=False, torch_dtype="float32")
SERVE = dict(batch_size=4, seq_len=96, pa_block_size=8, pa_num_blocks=48,
             context_encoding_buckets=[8, 16], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(35)
#: eight prompts for four rows: the later ones are admitted as rows finish
PROMPTS = [RNG.integers(1, 128, size=int(n)).tolist()
           for n in (9, 21, 5, 14, 7, 11, 3, 18)]
#: a budget of 1 (ends at admission), of 2 (ends at its first decode token),
#: and longer ones that end on different passes
BUDGETS = [6, 1, 2, 12, 5, 9, 3, 7]
STOP, CANCEL = 3, 5          # requests that end at a stop token / a cancel


def _attention_app():
    tcfg = TpuConfig(dtype="float32", **SERVE)
    app = PagedCausalLMApplication(
        None, LlamaInferenceConfig(tcfg, **LLAMA), LlamaFamily)
    return app.init_random_weights(35).init_cache()


def _granite_app():
    ref = build.load_reference("granitemoehybrid")
    w = weights.make_weights(ref.weight_shapes(granite_toy.HF),
                             seed=2**31 + 30)
    return granite_toy._app(ref, w)


def _delta_app():
    ref = build.load_reference("olmo_hybrid")
    w = weights.make_weights(ref.weight_shapes(delta_toy.HF),
                             seed=2**31 + 34)
    return delta_toy._app(ref, w)


def _olmoe_app():
    """OLMoE's keys at a toy size: attention and routed experts."""
    from neuronx_distributed_inference_tpu.models.family import get_family
    fam = get_family("olmoe")
    hf = dict(LLAMA, model_type="olmoe", intermediate_size=32,
              num_hidden_layers=3, num_key_value_heads=4, num_experts=8,
              num_experts_per_tok=2, norm_topk_prob=False)
    app = PagedCausalLMApplication(
        None, fam.config_cls(TpuConfig(dtype="float32", **SERVE), **hf), fam)
    return app.init_random_weights(63).init_cache()


STACKS = {"attention": _attention_app, "granite": _granite_app,
          "delta_rule": _delta_app, "moe": _olmoe_app}


@pytest.fixture(scope="module", params=list(STACKS))
def stack(request):
    return STACKS[request.param]()


#: ``prefill_budget_tokens`` of the budgeted runs: one chunk of the widest
#: bucket of every toy stack before each decode step
BUDGET = 16


def _serve(app, depth, stop_token=None, budget=None, **engine_kw):
    """One scripted run: four requests at once, the other four one a pass
    from pass 2 on; request STOP carries ``stop_token``; request CANCEL is
    cancelled once three of its tokens are out. Returns what each stream
    delivered and how it ended, the adapter and the engine."""
    ad = PagedEngineAdapter(app, pipeline_depth=depth,
                            prefill_budget_tokens=budget)
    eng = ServingEngine(ad, starvation_bound_s=1e9, **engine_kw)
    streams = {}

    def submit(i):
        stops = (stop_token,) if i == STOP and stop_token is not None else ()
        streams[i] = eng.submit(PROMPTS[i], BUDGETS[i], stop_tokens=stops,
                                request_id=f"r{i}")

    for i in range(4):
        submit(i)
    later = list(range(4, len(PROMPTS)))
    in_flight_at_cancel = None
    for n_pass in range(400):
        if n_pass >= 2 and later:
            submit(later.pop(0))
        eng.run_pass()
        victim = streams.get(CANCEL)
        if (victim is not None and not victim.finished
                and victim.n_tokens >= 3):
            in_flight_at_cancel = ad._inflight is not None
            assert eng.cancel(f"r{CANCEL}")
        if not later and not eng.has_work:
            break
    assert not eng.has_work and not app.kv_mgr.tables
    assert ad._inflight is None and not ad._ready and not ad._parked
    assert not ad._unwritten and not ad._state_slot
    got = {i: (list(s.tokens), s.finish_reason) for i, s in streams.items()}
    return got, ad, eng, in_flight_at_cancel


@pytest.mark.parametrize("budget", [None, BUDGET])
def test_default_engine_serves_the_eager_streams(stack, budget):
    free = stack.kv_mgr.allocator.num_free
    plain, *_ = _serve(stack, 0)
    stop_token = plain[STOP][0][4]         # the fifth token of request STOP
    eager, ad0, _, _ = _serve(stack, 0, stop_token=stop_token, budget=budget)
    ahead, ad1, eng1, cancelled_in_flight = _serve(
        stack, None, stop_token=stop_token, budget=budget)
    assert ahead == eager
    assert eager == _serve(stack, 0, stop_token=stop_token)[0]
    # what the script asked for did happen, on both sides
    assert ahead[1][1] == ahead[2][1] == "length"
    assert [len(ahead[i][0]) for i in (1, 2)] == [1, 2]
    assert ahead[STOP][1] == "stop" and ahead[STOP][0][-1] == stop_token
    assert len(ahead[STOP][0]) <= 5 < BUDGETS[STOP]
    assert ahead[CANCEL][1] == "cancelled" and len(ahead[CANCEL][0]) == 3
    assert cancelled_in_flight is True
    for i in set(range(len(PROMPTS))) - {STOP, CANCEL}:
        assert ahead[i][1] == "length" and len(ahead[i][0]) == BUDGETS[i]
    # (b) the engagement: a row that joins or leaves is carried, and no
    # admission and no finish drains the step in flight
    h0, h1 = ad0.host_stats, ad1.host_stats
    assert h0["overlapped_dispatches"] == 0
    assert not any(h0[k] for k in h0 if k.startswith(("pipeline_drains_",
                                                       "pipeline_carries_")))
    assert h1["overlapped_dispatches"] > 0
    assert h1["pipeline_carries_release"] > 0     # a finish / the cancel
    assert h1["pipeline_carries_admit"] > 0       # a row admitted mid-run
    assert h1["pipeline_drains_release"] == h1["pipeline_drains_admit"] == 0
    assert h1["pipeline_drains_preempt"] == 0
    if budget:
        # the first prompts' last chunks find no decode row to hide behind;
        # no later one is waited for
        assert (0 < h1["prefill_blocking_fetches"]
                < h0["prefill_blocking_fetches"])
    # a lookahead step whose row had just ended is the only extra work:
    # at most one row-step a request
    assert h0["dispatches"] <= h1["dispatches"] <= (h0["dispatches"]
                                                    + len(PROMPTS))
    # every overlapped dispatch was fetched one pass late, none blocked
    # at its own dispatch
    assert h1["blocking_fetches"] <= h1["dispatches"]
    assert stack.kv_mgr.allocator.num_free == free
    assert eng1.stats["completed"] == len(PROMPTS) - 1
    assert eng1.stats["cancelled"] == 1


def test_backpressure_bound_holds_with_a_token_in_flight(stack):
    """A token in flight counts as unread: a stream never runs further
    ahead of a slow consumer than ``max_unread_tokens``, and the streams
    are the eager ones."""
    def serve(depth):
        ad = PagedEngineAdapter(stack, pipeline_depth=depth)
        eng = ServingEngine(ad, starvation_bound_s=1e9, max_unread_tokens=2)
        slow = eng.submit(PROMPTS[0], 9)
        fast = eng.submit(PROMPTS[3], 9)
        read, worst = [], 0
        for n_pass in range(200):
            eng.run_pass()
            worst = max(worst, slow.unread)
            fast.drain()
            if n_pass % 4 == 3:
                read += slow.drain()
            if not eng.has_work:
                break
        read += slow.drain()
        assert not eng.has_work and not stack.kv_mgr.tables
        return (read, list(fast.tokens), slow.finish_reason), worst, ad

    eager, worst0, _ = serve(0)
    ahead, worst1, ad = serve(None)
    assert ahead == eager and ahead[2] == "length"
    assert worst0 <= 2 and worst1 <= 2
    assert ad.host_stats["overlapped_dispatches"] > 0
    # the slow row dropping out of the stepped set, and coming back, is a
    # live-set change of the caller's making
    assert ad.host_stats["pipeline_drains_liveset"] > 0


#: every XLA backend compile of the process, a helper program's included
#: (one listener for the module: jax has no public way to take one off)
_COMPILES = []


def _watch_compiles():
    if not _COMPILES:
        import jax
        _COMPILES.append("watching")
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *_a, **_k: _COMPILES.append(name)
            if name == "/jax/core/compile/backend_compile_duration" else None)


def test_nothing_but_warmed_step_programs_in_steady_state(stack):
    """(c): after ``precompile`` declared steady state the lookahead run
    shows no first-seen jit signature, and the decode step fed its ids on
    the device is the executable the host-fed one is."""
    report = precompile(stack)
    assert stack.warmup_state()["steady_state"]
    _watch_compiles()
    before = len(_COMPILES)
    try:
        got, ad, _, _ = _serve(stack, None)
        got_b, ad_b, _, _ = _serve(stack, None, budget=BUDGET)
    finally:
        stack.declare_steady_state(False)
    assert got_b == got
    for a in (ad, ad_b):
        assert a.host_stats["overlapped_dispatches"] > 0
        # the program that merges a carried step's ids ran, warmed
        assert a.host_stats["pipeline_carries_admit"] > 0
        assert a.host_stats["pipeline_carries_release"] > 0
    assert stack.warmup_state()["incidents"] == []
    assert _COMPILES[before:] == []
    assert report["n_graphs"] == len({(g["kind"], g["bucket"])
                                      for g in report["graphs"]})
    assert ("carry_ids", SERVE["batch_size"]) in {
        (g["kind"], g["bucket"]) for g in report["graphs"]}
    assert all(reason in ("length", "cancelled") for _, reason in got.values())


def test_counters_reach_the_registry(stack):
    reg = telemetry.MetricsRegistry()
    ad = PagedEngineAdapter(stack, telemetry=reg)
    eng = ServingEngine(ad, starvation_bound_s=1e9)
    a = eng.submit(PROMPTS[0], 6)
    b = eng.submit(PROMPTS[2], 3)
    eng.run_until_drained()
    assert (a.finish_reason, b.finish_reason) == ("length", "length")
    snap = reg.snapshot()["metrics"]
    over = snap[tmetrics.OVERLAPPED_DISPATCHES_TOTAL]["series"]
    assert sum(s["value"] for s in over) == \
        ad.host_stats["overlapped_dispatches"] > 0
    carries = {s["labels"]["cause"]: s["value"]
               for s in snap[tmetrics.PIPELINE_CARRIES_TOTAL]["series"]}
    assert carries == {c: ad.host_stats[f"pipeline_carries_{c}"]
                       for c in carries} and carries.get("release", 0) >= 1
    # what still drains, and reaches the registry under its cause: the
    # caller stepping another set of the running rows
    ad.add_requests([0, 1], [PROMPTS[0], PROMPTS[2]])
    ad.step_ahead()
    ad.step_ahead([0])
    ad.release([0, 1])
    snap = reg.snapshot()["metrics"]
    drains = {s["labels"]["cause"]: s["value"]
              for s in snap[tmetrics.PIPELINE_DRAINS_TOTAL]["series"]}
    assert drains == {c: ad.host_stats[f"pipeline_drains_{c}"]
                      for c in drains} and drains.get("liveset", 0) >= 1


# ---------------------------------------------------------------------------
# (d) failures one step behind
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def attention_app():
    return _attention_app()


def test_deferred_fetch_failure_fails_streams_once(attention_app,
                                                  monkeypatch):
    app = attention_app
    eager_eng = ServingEngine(PagedEngineAdapter(app, pipeline_depth=0))
    want = [eager_eng.submit(p, 8) for p in PROMPTS[:2]]
    eager_eng.run_until_drained()

    eng = ServingEngine(PagedEngineAdapter(app), starvation_bound_s=1e9)
    fatal = []
    inner = eng._fatal
    monkeypatch.setattr(eng, "_fatal",
                        lambda err: (fatal.append(err), inner(err))[1])
    streams = [eng.submit(p, 8) for p in PROMPTS[:2]]
    for _ in range(3):
        eng.run_pass()
    delivered = [list(s.tokens) for s in streams]
    assert all(len(d) == 3 for d in delivered)       # 1 + two late steps
    assert eng.adapter._inflight is not None
    with FAULTS.inject("pipeline_flush") as fp:
        with pytest.raises(StepFailure) as ei:
            eng.run_pass()
    assert fp.trips == 1 and ei.value.retry_safe is False
    assert fatal == [ei.value] and eng.closed
    for s, before, full in zip(streams, delivered, want):
        assert s.finished and s.finish_reason == "error"
        assert s.error is ei.value
        # nothing delivered was lost or repeated, nothing was added
        assert list(s.tokens) == before == list(full.tokens)[:3]
    # the rollback reached the last DELIVERED token
    ad = eng.adapter
    for sid, st in ad.seqs.items():
        assert st.position == len(st.tokens) - 1
        assert app.kv_mgr.lens[sid] == st.position
    ad.release(list(ad.seqs))
    assert not app.kv_mgr.tables

    async def forever():
        eng2 = ServingEngine(PagedEngineAdapter(app), starvation_bound_s=1e9)
        seen = []
        inner2 = eng2._fatal
        eng2._fatal = lambda err: (seen.append(err), inner2(err))[1]
        s = eng2.submit(PROMPTS[0], 30)
        with FAULTS.inject("pipeline_flush", nth=3):
            with pytest.raises(StepFailure):
                await eng2.run_forever()
        return s, seen, eng2

    s, seen, eng2 = asyncio.run(forever())
    assert len(seen) == 1 and s.finish_reason == "error"
    assert list(s.tokens) == list(want[0].tokens)[:s.n_tokens]
    eng2.adapter.release(list(eng2.adapter.seqs))
    assert not app.kv_mgr.tables


def test_a_row_at_seq_len_gets_its_last_token(attention_app):
    """Positions include the lookahead, so the ``seq_len`` guard trips one
    pass early, with the row's last token still in flight: it is delivered
    before the row ends with ``capacity``, as eager delivers it."""
    app = attention_app
    prompt = RNG.integers(1, 128, size=SERVE["seq_len"] - 4).tolist()

    def serve(depth):
        eng = ServingEngine(PagedEngineAdapter(app, pipeline_depth=depth),
                            starvation_bound_s=1e9)
        s = eng.submit(prompt, 40)
        other = eng.submit(PROMPTS[0], 12)
        eng.run_until_drained()
        assert not app.kv_mgr.tables
        return ((list(s.tokens), s.finish_reason),
                (list(other.tokens), other.finish_reason))

    eager, ahead = serve(0), serve(None)
    assert ahead == eager
    assert ahead[0][1] == "capacity" and len(ahead[0][0]) == 5
    assert ahead[1][1] == "length"


def test_a_pass_that_only_dispatched_is_not_idle(attention_app):
    """The pass that fills the pipeline delivers nothing; ``run_forever``
    must yield behind it, not take the idle nap."""
    from neuronx_distributed_inference_tpu.telemetry.trace import (
        disable_recorder, enable_recorder)
    rec = enable_recorder()
    try:
        async def main():
            eng = ServingEngine(PagedEngineAdapter(attention_app),
                                starvation_bound_s=1e9)
            task = asyncio.ensure_future(eng.run_forever(idle_sleep_s=0.2))
            s = eng.submit(PROMPTS[0], 6)

            async def consume():
                return [tok async for tok in s]
            got = await asyncio.wait_for(consume(), timeout=60)
            eng.close()
            await task
            return s, got
        s, got = asyncio.run(main())
        assert s.finish_reason == "length" and len(got) == 6
        names = [e["name"] for e in rec.events()]
        first_dispatch = names.index("dispatch.decode")
        busy = names[first_dispatch:len(names) - names[::-1].index(
            "trace.emit")]
        assert "loop.yield" in busy and "loop.idle" not in busy
    finally:
        disable_recorder()


# ---------------------------------------------------------------------------
# (e) a default adapter's prompts are paced between the decode steps
# ---------------------------------------------------------------------------

#: ``prefill_chunk_tokens`` of the paced runs, and prompts of 1, 3 and 9
#: chunks of it
CHUNK = 8
PACED = {n: RNG.integers(1, 128, size=size).tolist()
         for n, size in ((1, 7), (3, 21), (9, 69))}
PACED_BUDGETS = {1: 6, 3: 12, 9: 8}
#: the decode gaps the paced runs' rule looks back over (96 as served)
WINDOW = 6


def _alone(app, prompt, n):
    """``prompt``'s first ``n`` tokens from an adapter that serves nothing
    else: the blocking chain of a direct ``add_requests``, eager steps."""
    ad = PagedEngineAdapter(app, prefill_chunk_tokens=CHUNK)
    out = [ad.add_requests([0], [prompt])[0]]
    out += [ad.step()[0] for _ in range(n - 1)]
    ad.release([0])
    return out


def _paced_engine(app, depth=None):
    ad = PagedEngineAdapter(app, pipeline_depth=depth,
                            prefill_chunk_tokens=CHUNK)
    ad._pace_gaps = collections.deque(maxlen=WINDOW)
    eng = ServingEngine(ad, starvation_bound_s=1e9)
    # two rows decode: a long one, and one whose end frees a row mid-run
    rows = [eng.submit(PROMPTS[0], 60), eng.submit(PROMPTS[2], 10)]
    while ad.host_stats["decode_gaps"] < WINDOW:
        eng.run_pass()
    return ad, eng, rows


@pytest.mark.parametrize("depth", [None, 0], ids=["ahead", "eager"])
def test_paced_prompts_are_the_rows_served_alone(stack, depth):
    ad, eng, rows = _paced_engine(stack, depth)
    h = ad.host_stats
    # the ramp: nobody decoding, both prompts' chain at once, fetched
    assert (h["prefill_chains_whole"], h["prefill_paced_passes"]) == (1, 0)
    ramp_fetches = h["prefill_blocking_fetches"]
    ramp_dispatches = h["prefill_dispatches"]
    # nine chunks and three take the two free rows; one chunk waits for the
    # short row's end: all three are admitted while rows decode
    streams = {n: eng.submit(PACED[n], PACED_BUDGETS[n]) for n in (9, 3, 1)}
    worst = 0
    while eng.has_work:
        before = h["prefill_dispatches"]
        eng.run_pass()
        ran = h["prefill_dispatches"] - before
        if ran:
            assert 1 <= ran <= h["prefill_pace_k"]
            worst = max(worst, ran)
    for n, s in streams.items():
        assert s.finish_reason == "length"
        assert list(s.tokens) == _alone(stack, PACED[n], PACED_BUDGETS[n])
    assert list(rows[0].tokens) == _alone(stack, PROMPTS[0], 60)
    assert list(rows[1].tokens) == _alone(stack, PROMPTS[2], 10)
    # every chunk of the three went out between decode steps, no chain whole
    assert h["prefill_chains_whole"] == 1
    # (a dispatch packs the chunks of the prompts pending beside each other:
    # nine dispatches at least, thirteen at most)
    assert 9 <= h["prefill_paced_chunks"] <= 9 + 3 + 1
    assert h["prefill_paced_chunks"] == (h["prefill_dispatches"]
                                         - ramp_dispatches)
    assert 0 < h["prefill_paced_passes"] <= h["prefill_paced_chunks"]
    assert worst < 9 and h["prefill_pace_f"] > 0.0
    assert h["prefill_dispatches_in_gaps"] == h["prefill_paced_chunks"]
    if depth is None:
        # a last chunk behind a decode step is parked, not waited for
        assert h["prefill_blocking_fetches"] == ramp_fetches
        assert h["pipeline_carries_admit"] >= 1
        assert h["pipeline_drains_admit"] == 0
    assert not stack.kv_mgr.tables and not ad._parked and not ad._unwritten


def test_a_paced_chunk_fault_sends_its_prompt_back_to_the_queue(stack):
    """A fault between two paced chunks rolls the prompt back in the adapter
    (retry-safe); the engine queues it again, as it does a failed
    admission's batch, and the stream is the row served alone."""
    ad, eng, rows = _paced_engine(stack)
    s = eng.submit(PACED[3], 5)
    steps = ad.host_stats["dispatches"]
    eng.run_pass()                              # admitted, chunk 1 of 3
    sid = eng.seq_id_of(s.request_id)
    assert ad._chunks[sid].done == CHUNK and s.timeline.dispatch is not None
    # the chunk led in front of the step in flight: that step was fetched,
    # the next one held back for the pass after (k is 1: no second chunk)
    assert ad.host_stats["dispatches"] == steps and ad._inflight is None
    eng.run_pass()
    assert ad.host_stats["dispatches"] == steps + 1
    assert ad._chunks[sid].done == CHUNK and ad._inflight is not None
    with FAULTS.inject("prefill_chunk") as fp:
        eng.run_pass()                          # chunk 2 faults
    assert fp.trips == 1 and sid not in ad._chunks
    assert eng.stats["step_retries"] == eng.stats["admission_retries"] == 1
    assert eng.seq_id_of(s.request_id) is None and eng.queue.depth == 1
    assert s.timeline.admit is s.timeline.dispatch is None
    eng.run_until_drained()
    assert s.finish_reason == "length"
    assert list(s.tokens) == _alone(stack, PACED[3], 5)
    assert list(rows[0].tokens) == _alone(stack, PROMPTS[0], 60)
    assert not stack.kv_mgr.tables and not ad._unwritten


#: what a budgeted adapter dispatched for ``_budgeted_script`` BEFORE ISSUE 63
#: (recorded on that tree): ("chunk", rows, width) / ("decode", rows) as
#: enqueued, ("fetch", waited) where a last chunk's tokens were taken: waited
#: for at once (True), or parked and read behind a later step's fetch (False)
BUDGETED_DISPATCHES = [
    ("chunk", 1, 256), ("chunk", 1, 256), ("chunk", 4, 256), ("fetch", True),
    ("chunk", 4, 256), ("decode", 4), ("decode", 4), ("fetch", False),
    ("decode", 4), ("chunk", 1, 256), ("decode", 4), ("chunk", 1, 64),
    ("decode", 4), ("decode", 4), ("fetch", False), ("decode", 4),
    ("decode", 4), ("chunk", 1, 64), ("decode", 4), ("decode", 4),
    ("fetch", False), ("decode", 4), ("decode", 4), ("decode", 4),
    ("decode", 4)]


def _budgeted_script():
    """Six prompts of 10 to 600 tokens through a four-row engine whose
    adapter holds ``prefill_budget_tokens=256`` (buckets 64 and 256): four at
    once, two as rows free up; every dispatch and prefill fetch in order."""
    tcfg = TpuConfig(dtype="float32", **dict(
        SERVE, seq_len=640, pa_num_blocks=330,
        context_encoding_buckets=[64, 256]))
    app = PagedCausalLMApplication(
        None, LlamaInferenceConfig(tcfg, **LLAMA), LlamaFamily)
    app.init_random_weights(63).init_cache()
    ad = PagedEngineAdapter(app, prefill_budget_tokens=256)
    events = []
    run_paged, fetch = app._run_paged, ad._fetch_prefill_tokens

    def _run(ids, *a, **k):
        rows, width = ids.shape
        events.append(("decode", rows) if width == 1
                      else ("chunk", rows, width))
        return run_paged(ids, *a, **k)

    def _fetch(out, waited=True):
        events.append(("fetch", waited))
        return fetch(out, waited)

    app._run_paged, ad._fetch_prefill_tokens = _run, _fetch
    eng = ServingEngine(ad, starvation_bound_s=1e9)
    rng = np.random.default_rng(63)
    sizes = (600, 40, 300, 10, 257, 64)
    budgets = (8, 3, 6, 10, 5, 4)
    for size, n in zip(sizes, budgets):
        eng.submit(rng.integers(1, 128, size=size).tolist(), n)
    eng.run_until_drained()
    assert eng.stats["completed"] == len(sizes) and not app.kv_mgr.tables
    return events


def test_a_budgeted_adapter_issues_the_dispatches_it_always_issued():
    assert _budgeted_script() == BUDGETED_DISPATCHES
