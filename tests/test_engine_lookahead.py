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
      last token before it ends.
"""

import asyncio
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


STACKS = {"attention": _attention_app, "granite": _granite_app,
          "delta_rule": _delta_app}


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
