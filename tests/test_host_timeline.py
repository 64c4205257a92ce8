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
    tree = ast.parse(Path(trace_mod.__file__).read_text())
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
        if e["ph"] == "X":
            by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"]
    for want in ("pass.expire", "pass.preempt", "pass.admit",
                 "pass.dispatch", "loop.yield", "run.paged",
                 "fetch.tokens", "dispatch.prefill_chunk"):
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
    # self time (own seconds - seconds under it) is never negative: the
    # default adapter dispatches prefill inside pass.admit, decode inside
    # pass.dispatch, and the parent label tells the two run.paged apart
    for parent in ("pass.admit", "pass.dispatch", "dispatch.prefill_chunk"):
        assert 0.0 < under[parent] <= by_name[parent], parent
    assert ctr.get(span="dispatch.prefill_chunk", under="pass.admit") > 0
    assert ctr.get(span="run.paged", under="dispatch.prefill_chunk") > 0
    assert ctr.get(span="run.paged", under="pass.dispatch") > 0
    assert ctr.get(span="fetch.tokens", under="pass.dispatch") > 0


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
                 "loop.yield", "dispatch.prefill_chunk"):
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
