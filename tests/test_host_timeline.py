"""One timeline (ISSUE 25): the engine's own spans on the profiler's clock,
host seconds as a counter, named step programs and scopes, the front door's
SSE lag — on the tiny synthetic paged model shared with
test_serving_engine (CPU).

Pins:
  * inside a ``jax.profiler`` session the recorder's spans are TraceMe
    events on the ``/host:CPU`` plane of the xplane, tagged ``pass_id``;
  * ``nxdi_host_seconds_total{span=...}`` equals the recorder's own slice
    durations, label set bounded by the stable names;
  * ``pass.* | loop.*`` partition the serving loop's time;
  * the host's pass opened up (ISSUE 38): ``prep.inputs | prep.rng |
    prep.enqueue`` lie inside their ``run.paged``, ``dispatch.build``,
    ``dispatch.retire`` (around its ``fetch.tokens``) and
    ``deliver.tokens`` under ``pass.dispatch``;
  * the gap between two decode steps, by what it waited behind: exact
    ``host_stats`` counts with recorder and registry off, the same tokens
    on or off, no gap across an empty live set;
  * each new per-layer metric file of the benchmark reads a hand-built
    ``ctx``, and nothing (None or 0) from an empty one;
  * the lowered paged step is named after its function and carries the
    scopes ``embed``/``attn``/``mlp``/``lm_head``/``sample`` (``moe`` on
    an MoE model);
  * the disabled recorder never touches ``jax.profiler``;
  * ``nxdi_sse_lag_seconds`` counts every token the front door wrote.
"""

import ast
import asyncio
import glob
import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (ServingEngine,
                                                              ServingFrontend)
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")

TOP_LEVEL = trace_mod.ENGINE_PASS_PHASES + trace_mod.LOOP_EVENTS


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


def _prompts(seed, n, length=9):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=length).tolist() for _ in range(n)]


async def _serve(app, prompts, n_new=4):
    """The real serving loop: front door + ``run_forever``; returns the
    number of SSE token events each client read."""
    eng = ServingEngine(PagedEngineAdapter(app), starvation_bound_s=1e9)
    fe = ServingFrontend(eng)
    host, port = await fe.start()

    async def one(prompt):
        r, w = await asyncio.open_connection(host, port)
        body = json.dumps({"prompt": prompt,
                           "max_new_tokens": n_new}).encode()
        w.write(b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                + str(len(body)).encode() + b"\r\n\r\n" + body)
        await w.drain()
        data = (await asyncio.wait_for(r.read(), timeout=90)).decode()
        w.close()
        return data.count('"token"')
    try:
        return await asyncio.gather(*[one(p) for p in prompts])
    finally:
        await fe.stop()


# ---------------------------------------------------------------------------
# recorder semantics (no device work)
# ---------------------------------------------------------------------------

def test_span_carries_pass_id_and_late_args():
    rec = telemetry.enable_recorder()
    with rec.span("pass.admit"):
        pass
    assert "pass_id" not in rec.events()[-1]["args"]     # before any pass
    assert rec.next_pass() == 0 and rec.next_pass() == 1
    with rec.span("dispatch.prefill_chunk", cat="adapter", rows=2) as sp:
        sp.set(width=16)
    ev = rec.events()[-1]
    assert ev["ph"] == "X" and ev["dur"] >= 0.0
    assert ev["args"] == {"rows": 2, "width": 16, "pass_id": 1}
    # the shared no-op span takes late args too
    with trace_mod.NULL_RECORDER.span("x") as sp:
        sp.set(width=1)


def test_host_seconds_counter_label_set_is_bounded():
    reg = telemetry.enable()
    rec = telemetry.enable_recorder()
    rec.complete("pass.admit", 1.0, t1=1.25)
    rec.complete("pass.admit", 2.0, t1=2.5)
    rec.complete("not.a.stable.name", 0.0, t1=4.0)
    rec.complete("loop.idle", 3.0, t1=2.0)              # never negative
    with rec.span("pass.dispatch"):
        with rec.span("not.a.stable.name"):
            with rec.span("run.paged", cat="app"):
                pass
        rec.complete("fetch.tokens", 5.0, t1=5.5)       # retroactive
    ctr = reg.get(tmetrics.HOST_SECONDS_TOTAL)
    assert ctr.get(span="pass.admit", under="") == pytest.approx(0.75)
    assert ctr.get(span="other", under="") == pytest.approx(4.0)
    assert ctr.get(span="loop.idle", under="") == 0.0
    # the parent is the span open around it; an unstable parent reads ""
    assert ctr.get(span="fetch.tokens", under="pass.dispatch") == 0.5
    assert ctr.get(span="run.paged", under="") > 0.0
    assert ctr.get(span="other", under="pass.dispatch") > 0.0
    for s in ctr._snapshot():
        assert s["labels"]["span"] in set(trace_mod.EVENT_NAMES) | {"other"}
        assert s["labels"]["under"] in set(trace_mod.EVENT_NAMES) | {""}
    assert rec._open_spans() == []
    # registry off: slices still record, nothing is counted anywhere
    telemetry.disable()
    rec.complete("pass.admit", 0.0, t1=1.0)
    assert ctr.get(span="pass.admit", under="") == pytest.approx(0.75)


def test_disabled_recorder_never_touches_the_profiler(monkeypatch):
    """``NULL_RECORDER`` imports nothing from jax: ``telemetry/trace.py``
    has no module-level jax import, and a disabled span is the shared
    no-op whatever the profiler module does."""
    from neuronx_distributed_inference_tpu.telemetry import request_trace
    for mod in (trace_mod, request_trace):      # the timeline's module too
        tree = ast.parse(Path(mod.__file__).read_text())
        top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                      ast.ImportFrom))]
        names = [a.name for n in top if isinstance(n, ast.Import)
                 for a in n.names] + [n.module or "" for n in top
                                      if isinstance(n, ast.ImportFrom)]
        assert not [n for n in names if n.split(".")[0] == "jax"], names

    def boom(*a, **k):
        raise AssertionError("profiler touched with the recorder off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert not trace_mod.get_recorder().enabled
    null = trace_mod.NULL_RECORDER
    assert null.span("pass.dispatch") is null.span("loop.yield")
    with null.span("pass.dispatch", cat="engine", rows=1):
        pass
    # the marks of a request's timeline are the enabled recorder's alone
    assert null.mark("request.admit", "ab12") is None


# ---------------------------------------------------------------------------
# the serving loop under recorder + registry
# ---------------------------------------------------------------------------

def test_host_seconds_counter_equals_recorder_slices(paged_app):
    reg = telemetry.enable()
    rec = telemetry.enable_recorder(capacity=1 << 16)
    counts = asyncio.run(_serve(paged_app, _prompts(21, 3)))
    assert counts == [4, 4, 4]
    assert rec.dropped == 0
    by_name = {}
    for e in rec.events():
        # the request.* slices are a REQUEST's seconds, not this thread's:
        # in the ring, and in no counter (tests/test_request_timeline.py)
        if e["ph"] == "X" and e["cat"] != "request":
            by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"]
    assert sum(1 for e in rec.events() if e["cat"] == "request"
               and e["ph"] == "X") == 3 * 5
    for want in ("pass.expire", "pass.preempt", "pass.admit",
                 "pass.dispatch", "loop.yield", "run.paged",
                 "fetch.tokens", "dispatch.prefill_chunk",
                 "prep.inputs", "prep.rng", "prep.enqueue",
                 "dispatch.build", "dispatch.retire", "deliver.tokens"):
        assert want in by_name, want
    ctr = reg.get(tmetrics.HOST_SECONDS_TOTAL)
    counted, under = {}, {}
    for s in ctr._snapshot():
        lab = s["labels"]
        counted[lab["span"]] = counted.get(lab["span"], 0.0) + s["value"]
        under[lab["under"]] = under.get(lab["under"], 0.0) + s["value"]
    assert set(counted) == set(by_name)
    for name, total in by_name.items():
        assert counted[name] == pytest.approx(total, rel=1e-9, abs=1e-12)
    # the top of the loop thread's stack is pass.* / loop.* and nothing else
    tops = {s["labels"]["span"] for s in ctr._snapshot()
            if s["labels"]["under"] == ""}
    assert tops <= set(TOP_LEVEL), tops
    # self time (own seconds - seconds under it) is never negative: under
    # the engine the adapter dispatches prefill AND decode inside
    # pass.dispatch (admission runs no chunk: nothing opens under
    # pass.admit), and the parent label tells the two run.paged apart
    for parent in ("pass.dispatch", "dispatch.prefill_chunk",
                   "run.paged", "dispatch.retire"):
        assert 0.0 < under[parent] <= by_name[parent], parent
    assert "pass.admit" not in under
    assert ctr.get(span="dispatch.prefill_chunk", under="pass.dispatch") > 0
    assert ctr.get(span="run.paged", under="dispatch.prefill_chunk") > 0
    assert ctr.get(span="run.paged", under="pass.dispatch") > 0
    # the phases of run.paged are recorded under it and under nothing else
    for phase in ("prep.inputs", "prep.rng", "prep.enqueue"):
        assert ctr.get(span=phase, under="run.paged") == \
            pytest.approx(by_name[phase], rel=1e-9)
    # a decode step's fetch closes under dispatch.retire, a final prefill
    # chunk's under its dispatch.prefill_chunk; the parts of a decode
    # dispatch under pass.dispatch
    assert ctr.get(span="fetch.tokens", under="dispatch.retire") > 0
    assert ctr.get(span="fetch.tokens", under="pass.dispatch") == 0
    assert ctr.get(span="fetch.tokens", under="dispatch.prefill_chunk") > 0
    for part in ("dispatch.build", "dispatch.retire", "deliver.tokens"):
        assert ctr.get(span=part, under="pass.dispatch") == \
            pytest.approx(by_name[part], rel=1e-9)


def test_pass_and_loop_spans_partition_the_loop_thread(paged_app):
    rec = telemetry.enable_recorder(capacity=1 << 16)
    asyncio.run(_serve(paged_app, _prompts(22, 4), n_new=6))
    top = sorted((e for e in rec.events()
                  if e["ph"] == "X" and e["name"] in TOP_LEVEL),
                 key=lambda e: e["ts"])
    assert {"loop.yield", "loop.idle"} & {e["name"] for e in top}
    # top-level slices never overlap ...
    for a, b in zip(top, top[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-9, (a, b)
    # ... and leave next to nothing of the loop's time unnamed
    span = top[-1]["ts"] + top[-1]["dur"] - top[0]["ts"]
    covered = sum(e["dur"] for e in top)
    assert covered / span > 0.9, (covered, span)
    # every slice carries the id of the pass that caused it (the idle nap
    # before the first pass has none yet); ids only grow
    ids = [e["args"]["pass_id"] for e in top if "pass_id" in e["args"]]
    assert ids == sorted(ids) and ids[0] == 0
    assert all(e["name"] == "loop.idle" for e in top
               if "pass_id" not in e["args"])
    for e in rec.events():
        if e["ph"] == "X" and e["name"] in ("run.paged", "fetch.tokens"):
            # prefill dispatches under pass.admit, decode under pass.dispatch
            parent = [p for p in top
                      if p["name"] in ("pass.admit", "pass.dispatch")
                      and p["ts"] <= e["ts"]
                      and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-9]
            assert len(parent) == 1, e
            assert parent[0]["args"]["pass_id"] == e["args"]["pass_id"]


def test_prep_slices_lie_inside_their_run_paged(paged_app):
    """Every ``run.paged`` holds its three phases, in order, and they
    explain nearly all of it (what is left is opening and closing them)."""
    rec = telemetry.enable_recorder(capacity=1 << 16)
    asyncio.run(_serve(paged_app, _prompts(27, 2)))
    slices = sorted((e for e in rec.events() if e["ph"] == "X"),
                    key=lambda e: e["ts"])
    runs = [e for e in slices if e["name"] == "run.paged"]
    preps = [e for e in slices if e["name"].startswith("prep.")]
    assert runs and len(preps) == 3 * len(runs)
    for run in runs:
        lo, hi = run["ts"], run["ts"] + run["dur"]
        inside = [e for e in preps
                  if lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-9]
        assert [e["name"] for e in inside] == \
            ["prep.inputs", "prep.rng", "prep.enqueue"]
        for a, b in zip(inside, inside[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9
        assert sum(e["dur"] for e in inside) <= run["dur"] + 1e-9
        assert {e["args"].get("pass_id") for e in inside} == \
            {run["args"].get("pass_id")}
    # no span name but the eight run.<kind> starts with "run.": the
    # benchmark's host_spans.run_seconds sums the prefix
    assert [n for n in trace_mod.EVENT_NAMES if n.startswith("run.")] == \
        [n for n in trace_mod.APP_EVENTS if n.startswith("run.")]
    assert len([n for n in trace_mod.EVENT_NAMES
                if n.startswith("run.")]) == 8


# ---------------------------------------------------------------------------
# the gap between two decode steps, by what it waited behind
# ---------------------------------------------------------------------------

GAP_KEYS = ("decode_gaps", "decode_gap_s", "decode_gaps_behind_prefill",
            "decode_gap_s_behind_prefill", "prefill_dispatches_in_gaps",
            "decode_gaps_over_1s", "decode_gaps_over_1s_behind_prefill",
            "decode_gap_max_s")


def _admission_in_mid_decode(app, prompts, **adapter_kw):
    """One request decodes, a second is admitted once it does; returns
    the adapter, the streams' tokens and the prefill dispatches the second
    admission made."""
    adapter = PagedEngineAdapter(app, **adapter_kw)
    eng = ServingEngine(adapter, starvation_bound_s=1e9)
    first = eng.submit(prompts[0], 12)
    for _ in range(20):
        eng.run_pass()
        if adapter.host_stats["decode_gaps"] >= 2:     # it is decoding
            break
    assert adapter.host_stats["decode_gaps_behind_prefill"] == 0
    before = adapter.host_stats["prefill_dispatches"]
    second = eng.submit(prompts[1], 5)
    eng.run_until_drained()
    assert first.finish_reason == second.finish_reason == "length"
    return (adapter, [first.tokens, second.tokens],
            adapter.host_stats["prefill_dispatches"] - before)


@pytest.mark.parametrize("seed, adapter_kw", [
    (31, {}), (33, {"prefill_budget_tokens": 8}), (35, {"pipeline_depth": 0})],
    ids=["default", "chunked", "eager"])
def test_gap_counts_an_admission_in_mid_decode(paged_app, seed, adapter_kw):
    """Recorder and registry OFF: the keys are there and counted; the
    prefill dispatches of the mid-decode admission are exactly those seen
    in gaps (the first request's own were issued before any decode step)."""
    prompts = _prompts(seed, 2, length=19)     # fresh: no cached prefix
    assert not telemetry.get_registry().enabled
    assert not trace_mod.get_recorder().enabled
    adapter, tokens, chain = _admission_in_mid_decode(paged_app, prompts,
                                                      **adapter_kw)
    st = adapter.host_stats
    assert set(GAP_KEYS) <= set(st)
    assert chain >= (3 if adapter_kw.get("prefill_budget_tokens") else 1)
    assert st["prefill_dispatches_in_gaps"] == chain
    assert 1 <= st["decode_gaps_behind_prefill"] <= chain
    assert st["decode_gaps_behind_prefill"] < st["decode_gaps"]
    assert 0.0 < st["decode_gap_s_behind_prefill"] < st["decode_gap_s"]
    assert 0.0 < st["decode_gap_max_s"] <= st["decode_gap_s"]
    assert st["decode_gaps_over_1s"] == 0
    assert st["decode_gaps_over_1s_behind_prefill"] == 0
    # on: the same tokens (the prompts' prefixes are cached now, so the
    # chain may be shorter), counted the same way, and the histogram holds
    # the gaps by cause
    reg = telemetry.enable()
    telemetry.enable_recorder()
    adapter_on, tokens_on, chain_on = _admission_in_mid_decode(
        paged_app, prompts, **adapter_kw)
    assert tokens_on == tokens
    on = adapter_on.host_stats
    assert on["prefill_dispatches_in_gaps"] == chain_on >= 1
    assert 1 <= on["decode_gaps_behind_prefill"] <= chain_on
    hist = reg.get(tmetrics.DECODE_GAP_SECONDS)
    by_cause = {c: hist.count(engine="paged", behind=c)
                for c in ("prefill", "drain", "none")}
    assert sum(by_cause.values()) == on["decode_gaps"]
    assert by_cause["prefill"] == on["decode_gaps_behind_prefill"]
    assert hist.sum(engine="paged", behind="prefill") == \
        pytest.approx(on["decode_gap_s_behind_prefill"])


def test_no_gap_spans_an_empty_live_set(paged_app):
    """Two requests one after the other count the gaps of each and none
    between them: time with nothing in flight is no gap."""
    prompts = _prompts(32, 2)

    def gaps(order):
        adapter = PagedEngineAdapter(paged_app)
        eng = ServingEngine(adapter, starvation_bound_s=1e9)
        for p in order:
            stream = eng.submit(p, 6)
            eng.run_until_drained()
            assert stream.finish_reason == "length"
            assert not adapter.seqs
        return adapter.host_stats

    one, two = gaps(prompts[:1]), gaps(prompts)
    assert one["decode_gaps"] >= 3
    assert two["decode_gaps"] == 2 * one["decode_gaps"]
    assert two["decode_gaps_behind_prefill"] == 0


# ---------------------------------------------------------------------------
# the benchmark's readers of the new spans and counts
# ---------------------------------------------------------------------------

def _bench():
    bench = str(REPO / "benchmark")
    for p in (str(REPO), bench):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import readers, reduce_trace
    return readers, reduce_trace


def _ctx(host_seconds=(), stall_seconds=(), **host_stats):
    """A window in which the program counted ``host_stats`` and recorded
    ``host_seconds`` (``(span, under, seconds)``); nothing before it."""
    prom = {}
    if host_seconds:
        prom["nxdi_host_seconds_total"] = {"series": [
            {"labels": {"span": s, "under": u}, "value": v}
            for s, u, v in host_seconds]}
    if stall_seconds:
        prom["nxdi_host_stall_seconds_total"] = {"series": [
            {"labels": {"span": s}, "value": v} for s, v in stall_seconds]}
    return {"before": {"counters": {}, "prom": {}},
            "after": {"counters": {f"host_stats.{k}": v
                                   for k, v in host_stats.items()},
                      "prom": prom}}


_PASS = [("pass.dispatch", "", 1.0), ("run.paged", "pass.dispatch", 0.5),
         ("prep.inputs", "run.paged", 0.2), ("prep.rng", "run.paged", 0.15),
         ("prep.enqueue", "run.paged", 0.1),
         ("dispatch.build", "pass.dispatch", 0.05),
         ("dispatch.retire", "pass.dispatch", 0.3),
         ("fetch.tokens", "dispatch.retire", 0.26),
         ("deliver.tokens", "pass.dispatch", 0.02)]
_COUNTS = dict(dispatches=80, prefill_dispatches=20, decode_gaps=50,
               decode_gaps_behind_prefill=4, decode_gap_s=2.0,
               decode_gap_s_behind_prefill=0.4,
               prefill_dispatches_in_gaps=12)


@pytest.mark.parametrize("name, want, empty", [
    ("host.prep_inputs_ms_per_dispatch", 2.0, 0.0),
    ("host.prep_rng_ms_per_dispatch", 1.5, 0.0),
    ("host.prep_enqueue_ms_per_dispatch", 1.0, 0.0),
    ("host.dispatch_build_ms_per_dispatch", 0.5, 0.0),
    ("host.dispatch_retire_ms_per_dispatch", 0.4, None),
    ("host.deliver_ms_per_dispatch", 0.2, 0.0),
    ("sched.gaps_behind_prefill_share", 8.0, None),
    ("sched.stalled_gap_mean_ms", 100.0, None),
    ("sched.prefill_dispatches_per_stalled_gap", 3.0, None),
    ("host.stall_s", 9.5, 0.0),
    ("device.idle_prep_share", 20.0, None),
])
def test_a_new_layer_metric_reads_a_hand_built_ctx(name, want, empty,
                                                   monkeypatch):
    """``empty``: what the parent's program gives the reader, whose counts
    advance (it dispatches) but which has none of the spans or keys."""
    readers, reduce_trace = _bench()
    parent = _ctx([("pass.dispatch", "", 1.0),
                   ("run.paged", "pass.dispatch", 0.5),
                   ("fetch.tokens", "pass.dispatch", 0.3)],
                  dispatches=80, prefill_dispatches=20)
    if name != "device.idle_prep_share":
        ctx = _ctx(_PASS, [("prep.rng", 9.0), ("run.paged", 0.5)], **_COUNTS)
        assert readers.read_metric(name, ctx) == pytest.approx(want)
        assert readers.read_metric(name, parent) == empty
        return
    # the slice's xplane is read from the run's output directory: none here
    from harness import host_spans
    monkeypatch.setattr(host_spans, "slice_trace_dir",
                        lambda ctx, out_dir=None: None)
    assert readers.read_metric(name, parent) is empty
    mod = readers.load_module(str(REPO / "benchmark" / "layer_metrics"
                                  / (name + ".py")))
    Event = reduce_trace.Event
    planes = {"/device:TPU:0": {reduce_trace.OPS_LINE: [
        Event("fusion.1", 0.0, 1.0), Event("fusion.2", 3.0, 2.0),
        Event("fusion.3", 9.0, 1.0)]}}
    events = [Event("prep.inputs", 0.5, 1.5),      # idle 1.0 .. 2.0
              Event("prep.rng", 2.0, 0.5),         # idle, all of it
              Event("prep.enqueue", 4.0, 0.5),     # the device is busy
              Event("prep.enqueue", 8.5, 1.0)]     # idle 8.5 .. 9.0
    split = mod.idle_by_phase(planes, events)
    assert split == pytest.approx({"prep.inputs": 1.0, "prep.rng": 0.5,
                                   "prep.enqueue": 0.5, "window_s": 10.0})
    assert 100.0 * sum(split[p] for p in mod.PHASES) / split["window_s"] \
        == pytest.approx(want)
    assert mod.idle_by_phase(planes, []) is None
    assert mod.idle_by_phase({}, events) is None


def test_spans_land_on_the_profilers_host_plane(paged_app, tmp_path):
    """A profiler session on CPU around the live serving loop: the
    recorder's slices are TraceMe events of the xplane's host plane."""
    from jax.profiler import ProfileData
    telemetry.enable_recorder()
    asyncio.run(_serve(paged_app, _prompts(23, 1)))        # warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        asyncio.run(_serve(paged_app, _prompts(24, 2)))
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "no xplane written"
    host = [p for p in ProfileData.from_file(files[-1]).planes
            if p.name == "/host:CPU"]
    assert len(host) == 1
    found = {}
    for line in host[0].lines:
        for e in line.events:
            if e.name in trace_mod.EVENT_NAMES:
                found.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats).get("pass_id"), line.name))
    for want in ("pass.expire", "pass.preempt", "pass.admit",
                 "pass.dispatch", "run.paged", "fetch.tokens",
                 "loop.yield", "dispatch.prefill_chunk", "prep.inputs",
                 "prep.rng", "prep.enqueue", "dispatch.build",
                 "dispatch.retire", "deliver.tokens"):
        assert want in found, (want, sorted(found))
    # one thread's line carries them all, tagged with the pass that caused
    # each; a run.paged slice lies inside a stage of its own pass (prefill
    # under pass.admit, decode under pass.dispatch)
    assert len({ln for evs in found.values() for *_, ln in evs}) == 1
    assert all(pid is not None for name, evs in found.items()
               for _, _, pid, _ in evs if name != "loop.idle")
    stages = found["pass.admit"] + found["pass.dispatch"]
    for lo, hi, pid, _ in found["run.paged"] + found["fetch.tokens"]:
        assert [1 for slo, shi, spid, _ in stages
                if spid == pid and slo <= lo and hi <= shi] == [1]
    # the benchmark's reader of run.paged's phases finds them in this file
    # (device.idle_prep_share reads the host plane itself)
    readers, _ = _bench()
    mod = readers.load_module(str(REPO / "benchmark" / "layer_metrics"
                                  / "device.idle_prep_share.py"))
    phases = mod.prep_events(files[-1])
    assert {e.name for e in phases} == set(mod.PHASES)
    assert len(phases) == sum(len(found[p]) for p in mod.PHASES)
    assert all(e.dur >= 0.0 for e in phases)


def test_sse_lag_counts_every_token_written(paged_app):
    counts = asyncio.run(_serve(paged_app, _prompts(25, 1)))   # registry off
    assert telemetry.get_registry().get(tmetrics.SSE_LAG_SECONDS) is None
    reg = telemetry.enable()
    counts = asyncio.run(_serve(paged_app, _prompts(26, 3), n_new=5))
    assert counts == [5, 5, 5]
    lag = reg.get(tmetrics.SSE_LAG_SECONDS)
    assert lag.count() == 15
    assert 0.0 <= lag.sum() < 15 * 5.0
    assert "nxdi_sse_lag_seconds_bucket" in reg.render_prometheus()


def test_token_stream_stamps_only_while_the_registry_is_on():
    from neuronx_distributed_inference_tpu.serving.engine.streams import \
        TokenStream
    s = TokenStream("r0")
    s.put(1)
    assert s.take_put_time(0) is None
    telemetry.enable()
    s.put(2)
    t = s.take_put_time(1)
    assert isinstance(t, float) and s.take_put_time(1) is None   # once
    assert s.tokens == [1, 2]


# ---------------------------------------------------------------------------
# names on the device
# ---------------------------------------------------------------------------

def _lowered_paged(app, width):
    b = app.tpu_config.batch_size
    fn = app.get_compiled("paged_forward")
    args = (app.params, app.cache, jnp.zeros((b, width), jnp.int32),
            jnp.zeros((b, width), jnp.int32),
            jnp.full((b, width), -1, jnp.int32),
            jnp.zeros((b, app.max_blocks), jnp.int32),
            jnp.zeros((b,), jnp.int32), app._default_sampling_params(b),
            jax.random.PRNGKey(0))
    with app._mesh_ctx():
        return fn.lower(*args)


def _scopes(lowered):
    """Every scope-path component of the compiled program's op names
    (``jit(paged_forward_step)/while/body/closed_call/attn/dot_general``:
    the HLO metadata the profiler shows for each device operation)."""
    import re
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit_paged_forward_step"), text[:80]
    names = set(re.findall(r'op_name="([^"]+)"', text))
    return {part for n in names for part in n.split("/")[:-1]}, names


@pytest.mark.parametrize("width", [1, 16])
def test_paged_step_is_named_and_scoped(paged_app, width):
    scopes, names = _scopes(_lowered_paged(paged_app, width))
    assert "jit(paged_forward_step)" in scopes
    assert not [s for s in scopes if "_unknown" in s]
    assert {"embed", "attn", "mlp", "lm_head", "sample"} <= scopes
    assert "moe" not in scopes
    # the layer scan keeps the scopes of its body
    assert any("while/body" in n and "/attn/" in n for n in names)


def test_every_step_program_has_its_functions_name(paged_app):
    app = paged_app
    named = {
        "paged_forward_step": app._jit_paged(),
        "paged_decode_loop": app._jit_paged_loop(2),
        "paged_ragged_step": app._jit_ragged(False),
        "paged_spec_draft_loop": app._jit_spec_draft(2),
        "paged_spec_verify": app._jit_spec_verify(False),
        "context_encoding_step": app._jit_prefill(),
        "token_generation_step": app._jit_decode(),
        "decode_loop": app._jit_decode_loop(2),
    }
    for name, fn in named.items():
        assert fn.__name__ == name, (name, fn.__name__)


def test_moe_block_is_scoped_moe():
    """An MoE layer's router, expert matmuls and combine sit under ``moe``
    (dense layers under ``mlp``): the same one place, ``_mlp_block``."""
    from neuronx_distributed_inference_tpu.models.family import get_family
    fam = get_family("olmoe")
    hf = dict(HF, model_type="olmoe", num_experts=4, num_experts_per_tok=2,
              num_key_value_heads=4, norm_topk_prob=False)
    tcfg = TpuConfig(batch_size=2, seq_len=32, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[8],
                     is_block_kv_layout=True, pa_block_size=8)
    app = PagedCausalLMApplication(None, fam.config_cls(tcfg, **hf), fam)
    app.init_random_weights(3).init_cache()
    assert app.spec.moe is not None
    scopes, _ = _scopes(_lowered_paged(app, 1))
    assert {"moe", "attn", "lm_head", "sample"} <= scopes
    assert "mlp" not in scopes
