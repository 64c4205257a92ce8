"""A request's time to first token by phase (ISSUE 52): ONE timeline from
the socket to the first SSE write — on the tiny synthetic paged model
shared with test_serving_engine / test_host_timeline (CPU).

Pins:
  * through ``ServingFrontend`` the seven stamps are ordered, the five
    phases partition ``write - accept`` and ``engine.stats`` holds their
    sums, under the default adapter and under ``prefill_budget_tokens``,
    with recorder and registry OFF; the same tokens on or off; the
    recorder holds the five ``request.*`` slices and the registry the twin
    counters only when on;
  * a request requeued after a preemption, a ``submit_record``
    continuation, a replay attach and a non-streaming reply add nothing;
  * a rolled-back admission stamps anew;
  * request slices add nothing to ``nxdi_host_seconds_total``, the stall
    counters or ``recorder.stalls()``, and the self time of ``loop.yield``
    and ``pass.admit`` is what it is without them;
  * ``GET /v1/debug/trace/<id>`` serves five ``request.*`` slices that tile
    accept .. first write around ``trace.begin``;
  * one set of stamps: the SLO tracker's ``ttft`` sample, the end of
    ``nxdi_request_ttft_seconds``, the adapter's request span and index 0
    of ``nxdi_sse_lag_seconds`` are the record's instants;
  * inside a profiler session ``request.admit`` / ``request.token`` are
    TraceMe marks of the host plane, paired by their ``trace`` stat, and
    the benchmark's ``ttft.device_idle_share`` reader finds them there.
"""

import asyncio
import glob
import json
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.resilience import FAULTS
from neuronx_distributed_inference_tpu.resilience.preemption import Preempted
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (ServingEngine,
                                                              ServingFrontend)
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod
from neuronx_distributed_inference_tpu.telemetry.request_trace import (
    TIMELINE_PHASES, TIMELINE_STAMPS, RequestTimeline)
from neuronx_distributed_inference_tpu.telemetry.slo import (SLOPolicy,
                                                             SLOTracker)

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")

PHASES = tuple(p for p, _, _ in TIMELINE_PHASES)
SLICES = tuple(f"request.{p}" for p in PHASES)
TTFT_KEYS = ("ttft_requests", "ttft_server_s") + tuple(
    f"ttft_{p}_s" for p in PHASES)


@pytest.fixture(scope="module")
def paged_app():
    """Same shapes as test_serving_engine so every graph is warm in the
    persistent compile cache."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture(autouse=True)
def _observability_disabled_after():
    yield
    telemetry.disable()
    telemetry.disable_recorder()


def _prompts(seed, n, length=19):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=length).tolist() for _ in range(n)]


def _engine(app, **kw):
    adapter_kw = {k: kw.pop(k) for k in ("prefill_budget_tokens", "ragged")
                  if k in kw}
    eng = ServingEngine(PagedEngineAdapter(app, **adapter_kw),
                        starvation_bound_s=1e9, **kw)
    eng.written = []                 # streams, in the order first written
    counted = eng.first_token_written

    def first_token_written(stream):
        eng.written.append(stream)
        counted(stream)
    eng.first_token_written = first_token_written
    return eng


async def _http(host, port, method, path, body=None):
    r, w = await asyncio.open_connection(host, port)
    data = b"" if body is None else json.dumps(body).encode()
    w.write(f"{method} {path} HTTP/1.1\r\nContent-Length: {len(data)}"
            "\r\n\r\n".encode() + data)
    await w.drain()
    raw = (await asyncio.wait_for(r.read(), timeout=90)).decode()
    w.close()
    return raw.split("\r\n\r\n", 1)[1]


def _sse(text):
    events = [json.loads(line[len("data: "):])
              for line in text.split("\n") if line.startswith("data: ")]
    return ([e["token"] for e in events if "token" in e],
            next(e for e in events if e.get("done")))


async def _generate(eng, prompts, n_new=4, after=None):
    """POST /v1/generate for every prompt at once through a real front
    door; returns ``[(tokens, done event)]`` and what ``after(host, port)``
    returned while the front door was still up."""
    fe = ServingFrontend(eng)
    host, port = await fe.start()
    try:
        got = await asyncio.gather(*[
            _http(host, port, "POST", "/v1/generate",
                  {"prompt": p, "max_new_tokens": n_new}) for p in prompts])
        extra = await after(host, port) if after is not None else None
        return [_sse(g) for g in got], extra
    finally:
        await fe.stop()


def _check_partition(eng, n):
    """Every written stream's stamps are there and ordered, its five phases
    add up to write - accept, and the engine's sums are theirs."""
    assert len(eng.written) == n == eng.stats["ttft_requests"]
    sums = dict.fromkeys(TTFT_KEYS[1:], 0.0)
    for stream in eng.written:
        tl = stream.timeline
        stamps = [getattr(tl, name) for name in TIMELINE_STAMPS]
        assert all(isinstance(t, float) for t in stamps), stamps
        assert stamps == sorted(stamps), stamps
        assert tl.accept < tl.submit      # the front door's own reading
        phases = tl.phases()
        assert tuple(phases) == PHASES
        assert all(v >= 0.0 for v in phases.values())
        assert sum(phases.values()) == pytest.approx(tl.write - tl.accept,
                                                     rel=1e-12, abs=1e-12)
        sums["ttft_server_s"] += tl.write - tl.accept
        for p, v in phases.items():
            sums[f"ttft_{p}_s"] += v
    for key, want in sums.items():
        assert eng.stats[key] == pytest.approx(want, rel=1e-12), key
    assert sum(eng.stats[f"ttft_{p}_s"] for p in PHASES) == pytest.approx(
        eng.stats["ttft_server_s"], rel=1e-9)


# ---------------------------------------------------------------------------
# the record alone
# ---------------------------------------------------------------------------

def test_a_stamp_is_written_once_and_phases_need_all_of_them():
    tl = RequestTimeline()
    assert tl.phases() is None
    for i, name in enumerate(TIMELINE_STAMPS):
        assert getattr(tl, name) is None
        assert tl.stamp(name, float(i)) and not tl.stamp(name, 99.0)
        if name != "write":
            assert tl.phases() is None
    assert tl.phases() == {"accept": 1.0, "queue": 1.0, "prefill_wait": 1.0,
                           "prefill": 1.0, "write": 2.0}   # put is inside
    tl.continued = True
    assert tl.phases() is None


# ---------------------------------------------------------------------------
# through the front door, tracing off and on
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed, adapter_kw", [
    (41, {}), (43, {"prefill_budget_tokens": 8})], ids=["default", "chunked"])
def test_phases_partition_the_time_to_first_token(paged_app, seed,
                                                  adapter_kw):
    """Recorder and registry OFF: the seven keys are there and exact. ON:
    the same tokens, and only then the slices and the twin counters."""
    prompts = _prompts(seed, 3)
    assert not telemetry.get_registry().enabled
    assert not trace_mod.get_recorder().enabled
    eng = _engine(paged_app, **adapter_kw)
    assert set(TTFT_KEYS) <= set(eng.stats)
    assert all(not eng.stats[k] for k in TTFT_KEYS)
    off, _ = asyncio.run(_generate(eng, prompts))
    assert [len(t) for t, _ in off] == [4, 4, 4]
    _check_partition(eng, 3)
    # the prompts of one batch wait behind each other's chains (default),
    # or behind the budget's turns (chunked): the phase is not empty
    assert eng.stats["ttft_prefill_wait_s"] > 0.0
    assert eng.stats["ttft_prefill_s"] > 0.0
    assert len(trace_mod.get_recorder()) == 0

    reg = telemetry.enable()
    rec = telemetry.enable_recorder(capacity=1 << 16)
    eng_on = _engine(paged_app, **adapter_kw)
    on, _ = asyncio.run(_generate(eng_on, prompts))
    assert [t for t, _ in on] == [t for t, _ in off]     # bit-identical
    _check_partition(eng_on, 3)
    assert reg.get(tmetrics.TTFT_REQUESTS_TOTAL).get() == 3
    twin = reg.get(tmetrics.TTFT_PHASE_SECONDS_TOTAL)
    for p in PHASES:
        assert twin.get(phase=p) == pytest.approx(
            eng_on.stats[f"ttft_{p}_s"], rel=1e-12)
    assert {s["labels"]["phase"] for s in twin._snapshot()} == set(PHASES)
    slices = [e for e in rec.events() if e["cat"] == "request"
              and e["ph"] == "X"]
    assert sorted(e["name"] for e in slices) == sorted(SLICES * 3)
    for stream in eng_on.written:
        mine = [e for e in slices
                if e["args"]["request_id"] == stream.request_id]
        assert [e["name"] for e in mine] == list(SLICES)
        assert len({e["args"]["trace"] for e in mine}) == 1
        tl = stream.timeline
        assert mine[0]["ts"] == tl.accept
        assert mine[-1]["ts"] + mine[-1]["dur"] == pytest.approx(tl.write)


def test_a_replay_attach_and_a_plain_reply_add_nothing(paged_app):
    eng = _engine(paged_app)
    prompts = _prompts(45, 3)

    async def after(host, port):
        rid = json.loads(await _http(
            host, port, "POST", "/v1/submit",
            {"prompt": prompts[1], "max_new_tokens": 3}))["request_id"]
        replay = _sse(await _http(host, port, "GET", f"/v1/stream/{rid}"))
        plain = json.loads(await _http(
            host, port, "POST", "/v1/generate",
            {"prompt": prompts[2], "max_new_tokens": 3, "stream": False}))
        return replay, plain
    got, (replay, plain) = asyncio.run(_generate(eng, prompts[:1],
                                                 after=after))
    assert len(replay[0]) == 3 and len(plain["tokens"]) == 3
    assert eng.stats["completed"] == 3
    _check_partition(eng, 1)         # the one live SSE attach, nobody else


def test_a_requeued_request_and_a_continuation_add_nothing(paged_app):
    eng = _engine(paged_app)
    fe = ServingFrontend(eng)
    # a request's first token is written, then it is preempted and requeued
    stream = eng.submit(_prompts(46, 1)[0], 6, accept_t=time.perf_counter())
    eng.run_pass()
    assert stream.n_tokens >= 1
    fe._note_write(stream, 0)                   # the SSE writer's own call
    assert eng.stats["ttft_requests"] == 1
    stamps = {n: getattr(stream.timeline, n) for n in TIMELINE_STAMPS}
    assert all(t is not None for t in stamps.values())
    sums = {k: eng.stats[k] for k in TTFT_KEYS}
    eng.adapter.preempt(eng.seq_id_of(stream.request_id), reason="scheduler")
    eng.run_until_drained()
    assert eng.stats["preempt_requeues"] == 1
    assert stream.finish_reason == "length" and stream.n_tokens == 6
    fe._note_write(stream, 0)                   # a second live reader
    assert {n: getattr(stream.timeline, n) for n in TIMELINE_STAMPS} == stamps
    assert {k: eng.stats[k] for k in TTFT_KEYS} == sums
    # a continuation: its client saw a first token on another replica
    now = time.perf_counter()
    rec = Preempted(seq_id=7, tokens=tuple(_prompts(47, 1)[0]) + (5,),
                    prompt_len=19, n_generated=1, reason="failover",
                    deadline=now + 60.0,
                    meta={"tenant": "t", "priority": 0})
    cont = eng.submit_record(rec, 3)
    assert cont.timeline.continued
    eng.run_until_drained()
    assert cont.n_tokens == 3
    fe._note_write(cont, 0)
    assert cont.timeline.write is not None and cont.timeline.phases() is None
    assert {k: eng.stats[k] for k in TTFT_KEYS} == sums
    # ... while a record with nothing generated yet is a first token here
    fresh = eng.submit_record(
        Preempted(seq_id=8, tokens=tuple(_prompts(48, 1)[0]), prompt_len=19,
                  n_generated=0, reason="failover", deadline=now + 60.0,
                  meta={"tenant": "t", "priority": 0}), 2)
    eng.run_until_drained()
    fe._note_write(fresh, 0)
    assert eng.stats["ttft_requests"] == 2


@pytest.mark.parametrize("adapter_kw", [
    {}, {"prefill_budget_tokens": 8}, {"ragged": True}],
    ids=["default", "chunked", "ragged"])
def test_a_readmission_exports_its_own_recompute(paged_app, adapter_kw):
    """Registry ON: a request evicted after its first token keeps the
    stamps of its first admission, and the adapter's exported series (the
    ttft histogram, the span's ``first_token``, TPOT) take the instants of
    the admission they belong to, as before the timeline: a time to first
    token is never negative, a TPOT never holds the evicted time."""
    reg = telemetry.enable()
    eng = _engine(paged_app, **adapter_kw)
    fe = ServingFrontend(eng)
    stream = eng.submit(_prompts(55, 1)[0], 6, accept_t=time.perf_counter())
    while stream.n_tokens < 2:
        eng.run_pass()
    fe._note_write(stream, 0)
    stamps = {n: getattr(stream.timeline, n) for n in TIMELINE_STAMPS}
    assert all(t is not None for t in stamps.values())
    time.sleep(0.05)            # evicted time a TPOT would show, if it held it
    eng.adapter.preempt(eng.seq_id_of(stream.request_id), reason="scheduler")
    t_evicted = time.perf_counter()
    eng.run_until_drained()
    waited = time.perf_counter() - t_evicted
    assert eng.stats["preempt_requeues"] == 1
    assert stream.finish_reason == "length" and stream.n_tokens == 6
    assert {n: getattr(stream.timeline, n) for n in TIMELINE_STAMPS} == stamps
    assert eng.stats["ttft_requests"] == 1
    first, again = [s for s in reg.spans if s["name"] == "request"]
    assert [e["name"] for e in first["events"]] == ["first_token",
                                                    "preempted"]
    assert [e["name"] for e in again["events"]] == ["first_token",
                                                    "released"]
    for span in (first, again):
        token = span["events"][0]
        assert 0.0 < token["ttft_s"] == pytest.approx(token["t"])
        assert token["t"] <= span["events"][1]["t"]
    # the first admission's sample ends at the timeline's stamp; the
    # recompute's lies inside the time since the eviction
    assert first["events"][0]["ttft_s"] < stamps["token"] - stamps["admit"]
    assert again["events"][0]["ttft_s"] < waited
    ttft = reg.get(tmetrics.REQUEST_TTFT_SECONDS)
    assert ttft.count(engine="paged", tenant="default") == 2
    assert ttft.sum(engine="paged", tenant="default") == pytest.approx(
        first["events"][0]["ttft_s"] + again["events"][0]["ttft_s"])
    # TPOT: the re-admission's own first token -> its last decode step
    released = again["events"][1]
    assert released["decode_steps"] > 0
    tpot = reg.get(tmetrics.REQUEST_TPOT_SECONDS)
    assert tpot.count(engine="paged", tenant="default") == 1
    assert 0.0 < (tpot.sum(engine="paged", tenant="default")
                  * released["decode_steps"]) \
        <= released["t"] - again["events"][0]["t"]


def test_an_eviction_before_the_first_token_counts_as_queue(paged_app):
    """Under a prefill budget a prompt can be evicted between its chunks.
    It waits in the queue again, so the admission that holds stamps
    ``admit`` and ``dispatch`` anew: the evicted time is ``queue``, not
    ``prefill``."""
    eng = _engine(paged_app, prefill_budget_tokens=8)
    fe = ServingFrontend(eng)
    stream = eng.submit(_prompts(56, 1)[0], 3, accept_t=time.perf_counter())
    tl = stream.timeline
    eng.run_pass()                  # admitted, the first of three chunks out
    assert tl.admit <= tl.dispatch and tl.token is tl.put is None
    eng.adapter.preempt(eng.seq_id_of(stream.request_id), reason="scheduler")
    t_evicted = time.perf_counter()
    eng.run_until_drained()
    assert eng.stats["preempt_requeues"] == 1
    assert stream.finish_reason == "length" and stream.n_tokens == 3
    assert t_evicted < tl.admit <= tl.dispatch < tl.token <= tl.put
    fe._note_write(stream, 0)
    _check_partition(eng, 1)
    assert eng.stats["ttft_queue_s"] > t_evicted - tl.submit
    assert eng.stats["ttft_prefill_s"] < tl.write - t_evicted


def test_a_rolled_back_admission_stamps_anew(paged_app):
    eng = _engine(paged_app)
    stream = eng.submit(_prompts(49, 1)[0], 3)
    with FAULTS.inject("prefill_chunk") as fp:
        eng.run_pass()                  # admission fails typed, requeued
    assert fp.trips == 1 and eng.stats["admission_retries"] == 1
    tl = stream.timeline
    assert tl.accept == tl.submit       # no front door: the timeline starts
    assert tl.admit is tl.dispatch is tl.token is None
    t_retry = time.perf_counter()
    eng.run_until_drained()
    assert stream.finish_reason == "length"
    assert t_retry < tl.admit <= tl.dispatch < tl.token <= tl.put


# ---------------------------------------------------------------------------
# request slices are the request's seconds, not the thread's
# ---------------------------------------------------------------------------

def _host_spans():
    bench = str(REPO / "benchmark")
    for p in (str(REPO), bench):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import host_spans
    return host_spans


@pytest.mark.parametrize("with_slices", [False, True],
                         ids=["without", "with"])
def test_request_slices_take_nothing_from_the_threads_spans(with_slices):
    """The same spans with and without five slices of a 30 s request laid
    out while ``loop.yield`` is open (where the SSE writer runs): the
    counter, the stall list and the readers' self times do not move."""
    host_spans = _host_spans()
    reg = telemetry.enable()
    rec = telemetry.enable_recorder()
    t = time.perf_counter()
    with rec.span("pass.admit"):
        with rec.span("dispatch.prefill_chunk", cat="adapter"):
            pass
    with rec.span("loop.yield") as sp:
        if with_slices:
            for i, name in enumerate(SLICES):
                rec.complete(name, t - 30.0 + 6 * i, cat="request",
                             t1=t - 24.0 + 6 * i, trace="ab12",
                             request_id="r0")
    events = rec.events()
    assert len([e for e in events if e["cat"] == "request"]) == \
        (5 if with_slices else 0)
    assert rec.stalls() == [] and sp.stalled_s == 0.0
    assert reg.get(tmetrics.HOST_STALL_SECONDS_TOTAL) is None
    assert reg.get(tmetrics.HOST_STALLS_TOTAL) is None
    series = {(s["labels"]["span"], s["labels"]["under"]): s["value"]
              for s in reg.get(tmetrics.HOST_SECONDS_TOTAL)._snapshot()}
    assert set(series) == {("pass.admit", ""), ("loop.yield", ""),
                           ("dispatch.prefill_chunk", "pass.admit")}
    by_name = {e["name"]: e["dur"] for e in events if e["cat"] != "request"}
    assert by_name["loop.yield"] > 0.0
    assert host_spans.self_seconds(series, ("loop.yield",)) == \
        pytest.approx(by_name["loop.yield"], rel=1e-9)
    own = by_name["pass.admit"] - by_name["dispatch.prefill_chunk"]
    assert own > 0.0
    assert host_spans.self_seconds(series, host_spans.SCHED) == \
        pytest.approx(own, rel=1e-9)


def test_the_served_loops_counter_holds_no_request_slice(paged_app):
    reg = telemetry.enable()
    rec = telemetry.enable_recorder(capacity=1 << 16)
    eng = _engine(paged_app)
    asyncio.run(_generate(eng, _prompts(51, 2)))
    names = {e["name"] for e in rec.events() if e["ph"] == "X"}
    assert set(SLICES) <= names
    labels = [s["labels"] for s in
              reg.get(tmetrics.HOST_SECONDS_TOTAL)._snapshot()]
    thread = {n for n in names if not n.startswith("request.")}
    assert {lab["span"] for lab in labels} == thread
    assert {lab["under"] for lab in labels} <= thread | {""}
    assert rec.stalls() == []


# ---------------------------------------------------------------------------
# GET /v1/debug/trace/<id>
# ---------------------------------------------------------------------------

def test_debug_trace_serves_five_tiles_around_trace_begin(paged_app):
    telemetry.enable_recorder(capacity=1 << 16)
    eng = _engine(paged_app)

    async def after(host, port):
        rid = eng.written[0].request_id
        return json.loads(await _http(host, port, "GET",
                                      f"/v1/debug/trace/{rid}"))
    _, payload = asyncio.run(_generate(eng, _prompts(52, 2), after=after))
    events = [e for e in payload["traceEvents"] if e["ph"] != "M"]
    tiles = [e for e in events if e["name"].startswith("request.")]
    assert [e["name"] for e in tiles] == list(SLICES)
    assert all(e["ph"] == "X" and e["cat"] == "request" for e in tiles)
    for a, b in zip(tiles, tiles[1:]):             # each starts where the
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-3)  # us
    begin = next(e for e in events if e["name"] == "trace.begin")
    admit = next(e for e in events if e["name"] == "trace.admit")
    emit = next(e for e in events if e["name"] == "trace.emit")
    # trace.begin is recorded in submit: after request.accept's end, in
    # request.queue; the last tile ends at the first write, before the end
    assert tiles[0]["ts"] < tiles[1]["ts"] <= begin["ts"]
    assert begin["ts"] <= tiles[1]["ts"] + tiles[1]["dur"] + 1e-3
    assert tiles[-1]["ts"] + tiles[-1]["dur"] <= emit["ts"]
    # trace.admit's wait is the queue alone: the request.queue tile
    assert admit["args"]["wait_s"] * 1e6 == pytest.approx(tiles[1]["dur"],
                                                          abs=1e-3)
    tl = eng.written[0].timeline
    assert sum(e["dur"] for e in tiles) == pytest.approx(
        (tl.write - tl.accept) * 1e6, rel=1e-9)


# ---------------------------------------------------------------------------
# one set of stamps
# ---------------------------------------------------------------------------

def test_every_first_token_reader_reads_the_one_record(paged_app):
    reg = telemetry.enable()
    tracker = SLOTracker(SLOPolicy(targets={"ttft": 60.0}))
    eng = _engine(paged_app, slo=tracker)
    asyncio.run(_generate(eng, _prompts(53, 2), n_new=3))
    tls = [s.timeline for s in eng.written]
    # the SLO tracker's ttft: submit -> the first put
    assert sorted(tracker._windows[("default", "ttft")].values()) == \
        pytest.approx(sorted(tl.put - tl.submit for tl in tls), rel=1e-12)
    assert tracker.report()["tenants"]["default"]["tpot"]["n"] == 2
    # nxdi_request_ttft_seconds ends at the token stamp (it starts at
    # add_requests, after admit), and the adapter's span says the same
    ttft = reg.get(tmetrics.REQUEST_TTFT_SECONDS)
    assert ttft.count(engine="paged", tenant="default") == 2
    total = ttft.sum(engine="paged", tenant="default")
    assert total < sum(tl.token - tl.admit for tl in tls)
    spans = [s for s in reg.spans if s["name"] == "request"]
    firsts = [s["events"][0] for s in spans]
    assert [e["name"] for e in firsts] == ["first_token"] * 2
    assert sum(e["ttft_s"] for e in firsts) == pytest.approx(total)
    assert all(e["t"] == pytest.approx(e["ttft_s"]) for e in firsts)
    # index 0 of nxdi_sse_lag_seconds is put -> write of the record
    lag = reg.get(tmetrics.SSE_LAG_SECONDS)
    assert lag.count() == 6
    assert lag.sum() >= sum(tl.write - tl.put for tl in tls) > 0.0


# ---------------------------------------------------------------------------
# the device's clock
# ---------------------------------------------------------------------------

def test_admit_and_token_marks_land_on_the_profilers_host_plane(paged_app,
                                                                tmp_path):
    telemetry.enable_recorder()
    asyncio.run(_generate(_engine(paged_app), _prompts(54, 1)))      # warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    eng = _engine(paged_app)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        asyncio.run(_generate(eng, _prompts(55, 2)))
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "no xplane written"
    _host_spans()
    from harness import readers
    mod = readers.load_module(str(REPO / "benchmark" / "layer_metrics"
                                  / "ttft.device_idle_share.py"))
    marks = mod.mark_events(files[-1])
    assert sorted(e.name for e in marks) == ["request.admit"] * 2 \
        + ["request.token"] * 2
    pairs = mod.prefill_intervals(marks)
    assert len(pairs) == 2 and all(hi > lo for lo, hi in pairs)
    # ... the same two requests, on the recorder's clock
    want = sorted(s.timeline.token - s.timeline.admit for s in eng.written)
    assert sorted(hi - lo for lo, hi in pairs) == pytest.approx(want,
                                                                abs=2e-4)
    # none of them in the ring: the slices carry the instants
    rec = trace_mod.get_recorder()
    assert not [e for e in rec.events()
                if e["name"] in ("request.admit", "request.token")]
