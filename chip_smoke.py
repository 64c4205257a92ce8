#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

Drives the system's main path once, at the full width and depth of
Llama-3.2-1B (bf16, weights from a seed), through the entry points a user
calls::

    PagedCausalLMApplication(is_block_kv_layout, is_prefix_caching)
      -> serving.warmup.precompile(app)
      -> PagedEngineAdapter(app)            [then again with ragged=True]
      -> ServingEngine -> ServingFrontend on a localhost port
      -> concurrent POST /v1/generate SSE requests, GET /metrics

and checks what comes out by the repo's own means: every stream ends
``done``/``length`` with the asked number of in-vocabulary tokens; zero
step failures, preemptions and steady-state recompiles; the compiled T=1
graphs hold a Mosaic custom call; and a teacher-forced logit gate
(``utils/accuracy.check_accuracy_logits``, HF float32 CPU golden) passes on
the same application class at full width with depth cut to 2.

No chip, no result: unless ``jax.devices()[0].platform == "tpu"`` it exits
non-zero with one line saying why. One process, in-process clients, no
network, no children. Observations (set-up seconds, warm-up counts, peak
bytes) are printed for CHANGES.md; none of them is a metric.

``--tp 4`` runs the same body tensor-parallel over the four chips of one
host and adds the tp=1-vs-tp=4 logit comparison and the per-device bytes.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import tempfile
import time

T_START = time.perf_counter()

# Llama-3.2-1B as published (config.json of meta-llama/Llama-3.2-1B).
LLAMA_3_2_1B = dict(
    model_type="llama", hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
    head_dim=64, vocab_size=128256, rms_norm_eps=1e-5, rope_theta=500000.0,
    rope_scaling={"factor": 32.0, "high_freq_factor": 4.0,
                  "low_freq_factor": 1.0,
                  "original_max_position_embeddings": 8192,
                  "rope_type": "llama3"},
    max_position_embeddings=131072, hidden_act="silu",
    tie_word_embeddings=True)

# Serving shape. The pool is sized from what the compiler does with it, not
# from the toy tests: every paged graph at this geometry carries temps of
# about 2x the (k+v) pool (full-pool layout copies, ROADMAP A2), and a
# W-wide ragged step holds batch x W x vocab float32 logits. 1024 blocks of
# 32 is 1.07 GB of pool -> 2.15 GB of temps; the widest ragged row (256)
# adds 2.6 GB around its 1.05 GB of logits; weights are 2.5 GB: XLA's own
# account of the widest graph is 3.7 GB of arguments + 4.8 GB of temps, of
# the chip's 16.
SERVE = dict(batch_size=8, seq_len=2048, pa_block_size=32, pa_num_blocks=1024,
             context_encoding_buckets=[64, 256])
MAX_NEW_TOKENS = 24
# mixed prompt lengths, sent concurrently: two short, one past the first
# bucket, one past the largest bucket (so the adapter walks it in chunks).
# Then the last two again, concurrently, once their blocks are written — so
# a prefix hit happens (a repeat inside the SAME admission is cut back to a
# recompute: its blocks are not written yet).
PROMPT_LENS = (19, 57, 133, 300)
REPEAT_LENS = (133, 300)

# Logit gate: depth cut to 2 so the float32 CPU golden takes seconds.
GATE = dict(layers=2, batch=2, prompt_len=24, new_tokens=8)
# bf16 keeps 8 bits of mantissa: a logit of magnitude 4 (the largest of
# 128k near-Gaussian logits with sigma ~0.9) rounds by up to 2^-8 * 4 =
# 0.016 at the lm_head output alone, on top of what a 2-layer forward
# accumulates through bf16 matmul outputs and the bf16 KV cache. Over the
# gate's 2 million compared logits the largest error on a v5e was 0.047
# (PERF.md, PR 21); the bound is 1.7x that. An fp8 cache or fp8 matmuls
# (3 mantissa bits, 32x coarser) would overshoot it many times over.
GATE_ATOL = 0.08
GATE_RTOL = 0.02


class SmokeFailure(AssertionError):
    """A phase of the smoke did not hold."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - T_START:7.1f}s] {msg}",
          flush=True)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def build_app(hf_attrs, tp: int, model_path=None, **tcfg_kw):
    """A paged application on the first ``tp`` devices."""
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    tcfg = TpuConfig(tp_degree=tp, dtype="bfloat16", enable_bucketing=True,
                     is_block_kv_layout=True, is_prefix_caching=True,
                     **tcfg_kw)
    if model_path is not None:
        icfg = LlamaInferenceConfig(
            tcfg, load_config=load_pretrained_config(model_path))
    else:
        icfg = LlamaInferenceConfig(tcfg, **hf_attrs)
    return PagedCausalLMApplication(model_path, icfg, LlamaFamily)


def device_memory(n: int, when: str = ""):
    """``memory_stats()`` of the first ``n`` devices (None where the
    backend does not report them); printed when ``when`` names the moment."""
    import jax
    mem = [d.memory_stats() for d in jax.devices()[:n]]
    if when and all(mem):
        _say(f"memory {when}: bytes_in_use "
             f"{[m['bytes_in_use'] for m in mem]} peak_bytes_in_use "
             f"{[m['peak_bytes_in_use'] for m in mem]}")
    return mem


def mosaic_calls(app, kind: str) -> int:
    """Mosaic custom calls in the COMPILED T=1 graph of ``kind`` ("paged" or
    "ragged") — read from the executable, not from a flag. The lowering
    goes through the app's own jit wrapper and arguments; compiling it
    again is a persistent-cache load."""
    import jax.numpy as jnp
    import numpy as np
    b = app.tpu_config.batch_size
    bt = np.zeros((b, app._bt_buckets[-1]), np.int32)
    one = np.zeros((b, 1), np.int32)
    args = [app.params, app.cache, one, one, np.full((b, 1), -1, np.int32),
            bt]
    if kind == "paged":
        fn = app.get_compiled("paged_forward")
        args += [np.zeros((b,), np.int32)]
    else:
        fn = app._jit_ragged(False)
        args += [np.ones((b,), np.int32), np.zeros((b,), np.int32)]
    args += [app._default_sampling_params(b), app._next_rng()]
    with app._mesh_ctx():
        text = fn.lower(*[jnp.asarray(a) if isinstance(a, np.ndarray) else a
                          for a in args]).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# the numeric gate
# ---------------------------------------------------------------------------

def logit_gate(hf_attrs, tp_degrees, gate=GATE, atol=GATE_ATOL,
               rtol=GATE_RTOL):
    """Teacher-forced next-token logits of the paged application against
    the HF float32 CPU model, at full width with depth cut. Weights come
    from a seed, are rounded to bf16 once and saved, so both sides load the
    same numbers. With more than one entry in ``tp_degrees`` the later
    runs are also compared with the first (tp=1 vs tp=4)."""
    import os
    os.environ.setdefault("USE_TF", "0")    # transformers: torch only
    import numpy as np
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_inference_tpu.models.llama import LlamaFamily
    from neuronx_distributed_inference_tpu.utils import accuracy

    attrs = dict(hf_attrs, num_hidden_layers=gate["layers"])
    attrs.pop("model_type", None)
    torch.manual_seed(0)
    ids = np.random.default_rng(0).integers(
        1, attrs["vocab_size"], size=(gate["batch"], gate["prompt_len"]),
        dtype=np.int64)
    reports, logits = [], []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_gate_") as d:
        LlamaForCausalLM(LlamaConfig(**attrs)).to(torch.bfloat16) \
            .save_pretrained(d, safe_serialization=True)
        hf = LlamaFamily.load_hf_model(d).float().eval()
        bucket = -(-gate["prompt_len"] // 32) * 32
        for tp in tp_degrees:
            app = build_app(None, tp, model_path=d,
                            batch_size=gate["batch"], seq_len=2 * bucket,
                            pa_block_size=32,
                            pa_num_blocks=4 * gate["batch"],
                            context_encoding_buckets=[bucket],
                            output_logits=True)
            app.load_weights().init_cache()
            rep = accuracy.check_accuracy_logits(
                app, hf, ids, max_new_tokens=gate["new_tokens"],
                tol_map={i: (atol, rtol)
                         for i in range(gate["new_tokens"])})
            reports.append(rep)
            logits.append(rep.details["logits"])
            _say(f"logit gate tp={tp}: {rep}")
    cross = [max(float(np.abs(lg[k] - logits[0][k]).max()) for k in lg)
             for lg in logits[1:]]
    return reports, cross


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

async def _sse_generate(host, port, prompt, max_new_tokens):
    """One ``POST /v1/generate`` over a real socket; returns the parsed SSE
    ``data:`` events."""
    body = json.dumps({"prompt": prompt,
                       "max_new_tokens": max_new_tokens}).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout=600)
    finally:
        writer.close()
    text = raw.decode()
    _check("text/event-stream" in text,
           f"/v1/generate did not stream: {text[:200]!r}")
    return [json.loads(line[6:]) for line in text.splitlines()
            if line.startswith("data: ")]


async def _get(host, port, path):
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\n\r\n".encode())
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout=60)
    finally:
        writer.close()
    head, _, body = raw.decode().partition("\r\n\r\n")
    _check(head.startswith("HTTP/1.1 200"), f"GET {path}: {head[:80]!r}")
    return body


def _metric_sum(exposition: str, name: str) -> float:
    """Sum of every sample of ``name`` in a Prometheus text exposition."""
    total = 0.0
    for line in exposition.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in "{ ":
            total += float(line.rsplit(" ", 1)[1])
    return total


async def serve_round(app, waves, max_new_tokens, vocab, **adapter_kw):
    """Stand the front end up over ``app``, issue each wave's prompts as
    concurrent SSE requests from tasks in THIS process, scrape /metrics,
    and hold the round to the smoke's bar. Returns the token streams, in
    order, the prefix-hit tokens each wave added, and the HBM ledger
    (``GET /v1/debug/memory``)."""
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.engine import (
        ServingEngine, ServingFrontend)
    adapter = PagedEngineAdapter(app, **adapter_kw)
    engine = ServingEngine(adapter)
    frontend = ServingFrontend(engine)
    host, port = await frontend.start()
    hit_name = "nxdi_prefix_cache_hit_tokens_total"
    results, hits = [], []
    try:
        seen = _metric_sum(await _get(host, port, "/metrics"), hit_name)
        for prompts in waves:
            tasks = [asyncio.ensure_future(
                _sse_generate(host, port, p, max_new_tokens))
                for p in prompts]
            results += await asyncio.gather(*tasks)
            metrics = await _get(host, port, "/metrics")
            hits.append(_metric_sum(metrics, hit_name) - seen - sum(hits))
        state = json.loads(await _get(host, port, "/v1/debug/state"))
        ledger = json.loads(await _get(host, port, "/v1/debug/memory"))
    finally:
        await frontend.stop()
    streams = []
    for i, events in enumerate(results):
        _check(events and events[-1].get("done") is True,
               f"request {i}: stream did not end with a done event")
        done, toks = events[-1], [e["token"] for e in events[:-1]]
        _check(done.get("reason") == "length",
               f"request {i}: finished {done.get('reason')!r}, not 'length'")
        _check(len(toks) == max_new_tokens,
               f"request {i}: {len(toks)} tokens, asked {max_new_tokens}")
        _check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
               f"request {i}: token outside the vocabulary")
        streams.append(toks)
    for name in ("nxdi_step_failures_total", "nxdi_preemptions_total",
                 "nxdi_steady_state_recompiles_total"):
        _check(_metric_sum(metrics, name) == 0,
               f"/metrics: {name} = {_metric_sum(metrics, name)}")
    stats = engine.stats
    _check(stats["completed"] == len(results)
           and not (stats["preempt_requeues"] or stats["priority_preemptions"]
                    or stats["step_retries"] or stats["admission_retries"]),
           f"engine stats: {stats}")
    warm = state["engine"]["warmup"]
    _check(warm["steady_state"] and not warm["incidents"],
           f"steady-state recompiles: {warm['incidents']}")
    _say(f"served {len(results)} requests "
         f"({'ragged' if adapter_kw.get('ragged') else 'default'} adapter): "
         f"all 'length'; host_stats {dict(adapter.host_stats)}; "
         f"prefix-hit tokens per wave {[int(h) for h in hits]}")
    return streams, hits, ledger


def smoke(device, tp: int, hf_attrs=LLAMA_3_2_1B, serve=SERVE,
          prompt_lens=PROMPT_LENS, repeat_lens=REPEAT_LENS,
          max_new_tokens=MAX_NEW_TOKENS, gate=GATE):
    """The whole body. Raises on any failed phase; returns the
    observations it printed."""
    import jax
    import numpy as np
    from neuronx_distributed_inference_tpu import native, telemetry
    from neuronx_distributed_inference_tpu.ops import kernel_mode
    from neuronx_distributed_inference_tpu.serving.warmup import precompile
    from neuronx_distributed_inference_tpu.telemetry import observatory
    from neuronx_distributed_inference_tpu.utils.compile_cache import \
        configure_compile_cache

    on_chip = device["platform"] == "tpu"
    _check(device["count"] >= tp, f"--tp {tp} needs {tp} devices")
    if on_chip:
        _check(not kernel_mode.pallas_interpret(),
               "interpret-mode kernels were requested "
               f"({kernel_mode.INTERPRET_ENV}=1) — the smoke runs the real "
               "ones")
    cache_dir = configure_compile_cache()
    _say(f"compile cache: {cache_dir}")

    # -- set-up: weights, pool, the warm-up walk ---------------------------
    telemetry.enable()
    spmd = {"spmd_warnings": 0, "involuntary_remat": 0}
    t0 = time.perf_counter()
    with observatory.capture_compiler_stderr(spmd):
        app = build_app(hf_attrs, tp, **serve)
        app.init_random_weights(seed=0).init_cache()
        jax.block_until_ready((app.params, app.cache))
        t_init = time.perf_counter() - t0
        allocator = type(app.kv_mgr.allocator).__name__
        _say(f"weights + pool up in {t_init:.1f}s; allocator {allocator} "
             f"(native enabled: {native.native_enabled()})")
        device_memory(tp, "after weights + pool")
        report = precompile(app)
        device_memory(tp, "after precompile")
    setup_s = time.perf_counter() - t0
    _say(f"precompile: {report['n_graphs']} graphs — "
         f"{report['n_compiles']} XLA builds, "
         f"{report['n_cache_loads']} cache loads, "
         f"{report['n_warm_hits']} warm — in {report['total_seconds']:.1f}s; "
         f"set-up {setup_s:.1f}s")
    _say(f"kernel paths: {report['kernels']}")
    _check(spmd["involuntary_remat"] == 0,
           f"compile log: {spmd['involuntary_remat']} involuntary full "
           "rematerializations")
    if native.native_enabled():
        _check(allocator == "NativeBlockAllocator",
               f"native allocator expected, {allocator} is live")
    if on_chip:
        _check({"site": "paged_decode", "path": "pallas", "reason": ""}
               in report["kernels"] and len(report["kernels"]) == 1,
               f"decode did not take the compiled kernel: "
               f"{report['kernels']}")
        for kind in ("paged", "ragged"):
            n = mosaic_calls(app, kind)
            _say(f"compiled T=1 {kind} graph: {n} Mosaic custom call(s)")
            _check(n >= 1, f"T=1 {kind} graph holds no Mosaic custom call")

    # -- serve: default adapter, then the ragged one, same requests --------
    rng = np.random.default_rng(0)
    vocab = hf_attrs["vocab_size"]
    base = {n: rng.integers(1, vocab, size=n).tolist() for n in prompt_lens}
    waves = [[base[n] for n in prompt_lens], [base[n] for n in repeat_lens]]
    prompts = waves[0] + waves[1]
    block = serve["pa_block_size"]
    want_hit = sum((n - 1) // block * block for n in repeat_lens)
    rounds = []
    for kw in ({}, {"ragged": True}):
        streams, hits, ledger = asyncio.run(serve_round(
            app, waves, max_new_tokens, vocab, **kw))
        _check(hits[0] == 0 and hits[1] == want_hit,
               f"prefix-hit tokens per wave {hits}, expected "
               f"[0, {want_hit}]")
        rounds.append(streams)
        app.init_cache()      # cold prefix state: the next round prefills
    served, ragged = rounds

    # -- bit-identity: a finding, not a gate (the logit gate carries
    # correctness; CPU tier-1 pins these equal in float32) -----------------
    fits = [i for i, p in enumerate(prompts)
            if len(p) <= app.ctx_buckets[-1]]
    golden = {i: app.generate(np.asarray([prompts[i]], np.int32),
                              max_new_tokens=max_new_tokens
                              )["generated"][0].tolist() for i in fits}
    def first_diff(a, b):
        return next((j for j, (x, y) in enumerate(zip(a, b)) if x != y), None)

    diff_gen = [first_diff(served[i], golden[i]) for i in fits]
    diff_rag = [first_diff(a, b) for a, b in zip(served, ragged)]
    same_gen, same_rag = diff_gen.count(None), diff_rag.count(None)
    _say(f"served == app.generate bit for bit: {same_gen}/{len(fits)} "
         f"prompts that fit a ctx bucket (first differing token index per "
         f"prompt: {diff_gen}); default == ragged adapter: "
         f"{same_rag}/{len(prompts)} ({diff_rag})"
         + ("" if same_gen == len(fits) and same_rag == len(prompts)
            else " — NOT bit-identical on this device; the logit gate "
                 "below carries correctness"))
    _check(not app.warmup_state()["incidents"],
           f"steady-state recompiles: {app.warmup_state()['incidents']}")

    # -- memory, beside what was asked for ---------------------------------
    params_b, pool_b = ledger["model_bytes"], ledger["kv"]["pool_bytes"]
    mem = device_memory(tp, "after serving")
    _say(f"params {params_b:,} B + pool {pool_b:,} B = "
         f"{params_b + pool_b:,} B over {tp} device(s)")
    del app

    # -- the numeric gate ---------------------------------------------------
    reports, cross = logit_gate(hf_attrs, [1, tp] if tp > 1 else [1],
                                gate=gate)
    for rep in reports:
        _check(rep.passed, f"logit gate failed: {rep}")
    for d in cross:
        _say(f"tp=1 vs tp={tp} logits: max |diff| {d:.4f}")
        _check(d <= GATE_ATOL, f"tp=1 vs tp={tp} logits differ by {d}")
    return {
        "tp": tp, "setup_seconds": round(setup_s, 1),
        "init_seconds": round(t_init, 1),
        "precompile": {k: report[k] for k in (
            "n_graphs", "n_compiles", "n_cache_loads", "n_warm_hits")},
        "precompile_seconds": round(report["total_seconds"], 1),
        "params_bytes": params_b, "pool_bytes": pool_b,
        "peak_bytes_in_use": [m and m["peak_bytes_in_use"] for m in mem],
        "bytes_in_use": [m and m["bytes_in_use"] for m in mem],
        "served_equals_generate": f"{same_gen}/{len(fits)}",
        "default_equals_ragged": f"{same_rag}/{len(prompts)}",
        "gate_max_error": [round(r.max_error, 4) for r in reports],
        "tp_cross_max_diff": [round(d, 4) for d in cross],
        "allocator": allocator,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (4 on the four-chip host)")
    args = ap.parse_args(argv)
    from neuronx_distributed_inference_tpu.utils.device import (
        NoAcceleratorError, require_tpu, versions)
    try:
        device = require_tpu()
    except NoAcceleratorError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    _say(f"device: {device}; versions: {versions()}")
    try:
        obs = smoke(device, args.tp)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    _say(f"observations: {json.dumps(obs)}")
    _say(f"done in {time.perf_counter() - T_START:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
