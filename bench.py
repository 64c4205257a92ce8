#!/usr/bin/env python
"""Benchmark driver entry point — prints ONE JSON line with the headline
metric (decode throughput, reference harness schema: utils/benchmark.py
throughput = generated tokens / wall time).

The default mode runs on one TPU chip and fails without one (the ``--*``
report modes are CPU counters on toy models). It measures ONE cell on the
contiguous-cache path the serving engine does not use; the benchmark of
cells through ``ServingEngine`` is ROADMAP A1. ``chip_smoke.py`` is the
standing check that the serving path starts on the chip.
Model: Llama-3.2-1B-shaped decoder with synthetic bf16 weights (real 8B does
not fit a single 16GB chip alongside its KV cache; shapes are real, weights
random — throughput is weight-independent).

vs_baseline = measured tok/s / HBM-bandwidth roofline tok/s for this chip
(decode is bandwidth-bound: every step streams all params + KV once).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.compat import force_cpu_devices
from neuronx_distributed_inference_tpu.utils.compile_cache import \
    configure_compile_cache
from neuronx_distributed_inference_tpu.utils.device import (
    V5E, NoAcceleratorError, device_peaks, require_tpu)


def _tiny_llama_hf():
    """The synthetic tiny-llama config every CPU microbench builds (one
    copy here; scripts/check_spmd_sharding.py pins its own — the lint
    must stay runnable standalone)."""
    return dict(model_type="llama", hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, vocab_size=512,
                rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
                tie_word_embeddings=False, torch_dtype="float32")


def host_overhead_main():
    """CPU-runnable host-overhead microbench (ISSUE 3): drives the CB
    serving adapter's decode paths on a tiny synthetic model and reports
    host-ms/token, dispatches/token and host-blocking syncs/token for
    eager step(), pipelined step() (pipeline_depth=1) and step_many(8) —
    one parseable JSON line, no TPU required. The syncs/dispatches numbers
    are structural (counted at the adapter boundary), so they hold on any
    backend; the ms numbers are measured on whatever device runs."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import \
        ContinuousBatchingAdapter

    hf = _tiny_llama_hf()
    batch, n_steps, chunk = 2, 48, 8
    tcfg = TpuConfig(batch_size=batch, seq_len=128, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_continuous_batching=True)
    app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                              LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 500, size=8).tolist() for _ in range(batch)]
    sids = list(range(batch))

    def run(mode):
        eng = ContinuousBatchingAdapter(
            app, pipeline_depth=1 if mode == "pipelined" else 0)
        eng.add_requests(sids, prompts)
        base = dict(eng.host_stats)
        t0 = time.perf_counter()
        if mode == "step_many8":
            for _ in range(n_steps // chunk):
                eng.step_many(chunk)
        else:
            for _ in range(n_steps):
                eng.step()
            if mode == "pipelined":
                eng.flush()
        wall = time.perf_counter() - t0
        stats = {k: eng.host_stats[k] - base[k] for k in base}
        eng.release(sids)
        toks = n_steps * batch
        # host_blocked = host wall spent stalled inside blocking fetches —
        # the host-overhead number proper. wall additionally includes the
        # device compute itself (which on a CPU-only box shares the cores,
        # so overlap cannot shorten it the way it does on a real TPU).
        return {
            "host_blocked_ms_per_token": round(
                stats["blocked_s"] * 1e3 / toks, 4),
            "wall_ms_per_token": round(wall * 1e3 / toks, 4),
            "dispatches_per_token": round(stats["dispatches"] / toks, 4),
            "blocking_syncs_per_token": round(
                stats["blocking_fetches"] / toks, 4),
        }

    modes = ("eager", "pipelined", "step_many8")
    for m in modes:
        run(m)                         # warm: compile every graph
    results = {m: run(m) for m in modes}
    ratio = (results["eager"]["blocking_syncs_per_token"]
             / results["step_many8"]["blocking_syncs_per_token"])
    print(json.dumps({
        "metric": "host_overhead_syncs_stepmany8_vs_eager",
        "value": round(ratio, 2),
        "unit": "x_fewer_host_blocking_syncs",
        "details": {
            **{m: results[m] for m in modes},
            "decode_steps_per_mode": n_steps,
            "batch": batch,
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }))


def prefill_overhead_main(artifact_path="artifacts/bench_prefill_r07.json"):
    """CPU-runnable prefill microbench (ISSUE 5): monolithic vs
    chunked+packed paged admission of a skewed-length batch — padded-token
    work (the pad waste ragged prefill reclaims) and host-blocking sync
    counts, measured at the adapter boundary so the structural numbers
    hold on any backend. One parseable JSON line + an artifact file."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter

    hf = _tiny_llama_hf()
    # 2-D bucketing: a lone straggler row pads to batch bucket 1, not 2 —
    # half the packed path's win for skewed batches
    tcfg = TpuConfig(batch_size=2, seq_len=192, dtype="float32",
                     enable_bucketing=True, enable_2d_bucketing=True,
                     context_encoding_buckets=[16, 32, 64, 128],
                     is_block_kv_layout=True, pa_block_size=16,
                     is_prefix_caching=False)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    rng = np.random.default_rng(0)
    # the skewed batch monolithic admission pads worst: short + long
    prompts = [rng.integers(1, 500, size=n).tolist() for n in (8, 120)]
    sids = [0, 1]

    def run(chunk):
        eng = PagedEngineAdapter(app, prefill_chunk_tokens=chunk)
        t0 = time.perf_counter()
        eng.add_requests(sids, prompts)
        wall_ms = (time.perf_counter() - t0) * 1e3
        stats = dict(eng.host_stats)
        eng.release(sids)
        real = stats["prefill_real_tokens"]
        padded = stats["prefill_padded_tokens"]
        return {
            "prefill_dispatches": stats["prefill_dispatches"],
            "real_prompt_tokens": real,
            "padded_prompt_tokens": padded,
            "pad_waste_frac": round(1.0 - real / padded, 4),
            "host_blocking_syncs": stats["prefill_blocking_fetches"],
            "wall_ms": round(wall_ms, 2),
        }

    modes = {"monolithic": None, "chunked_packed": 16}
    for chunk in modes.values():
        run(chunk)                     # warm: compile every chunk width
    results = {name: run(chunk) for name, chunk in modes.items()}
    ratio = (results["monolithic"]["padded_prompt_tokens"]
             / results["chunked_packed"]["padded_prompt_tokens"])
    payload = {
        "metric": "prefill_padded_tokens_monolithic_vs_chunked_packed",
        "value": round(ratio, 2),
        "unit": "x_fewer_padded_prompt_tokens",
        "details": {
            **results,
            "prompt_lens": [len(p) for p in prompts],
            "prefill_chunk_tokens": modes["chunked_packed"],
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    print(json.dumps(payload))
    try:
        os.makedirs(os.path.dirname(artifact_path), exist_ok=True)
        with open(artifact_path, "w") as f:
            json.dump(payload, f, indent=1)
    except OSError as e:  # pragma: no cover - diagnostics only
        print(f"prefill-overhead artifact write failed: {e}",
              file=sys.stderr)


def spec_overhead_main(artifact_path="artifacts/bench_spec_r10.json"):
    """CPU-runnable speculative-decode microbench (ISSUE 9): drives the
    paged adapter's decode paths on the tiny synthetic model and reports
    dispatches-per-100-tokens and host-blocked ms/token for eager
    step(), step_many(8) and self-drafting speculation (k=3 and k=7,
    greedy — accept rate pinned at 1.0 because the target drafts its own
    continuation). The dispatch/sync numbers are structural (counted at
    the adapter boundary), so they hold on any backend; the ms numbers
    are measured on whatever device runs. One parseable JSON line + an
    artifact file, no TPU required. Headline = eager/spec_k3 dispatch
    ratio: 2.0x at accept 1.0 (one draft + one verify dispatch deliver
    k+1 tokens vs k+1 eager dispatches)."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.speculation import \
        SelfDraftProposer

    hf = _tiny_llama_hf()
    batch, n_decode = 2, 48          # divisible by 8 and by k+1 = 4, 8
    tcfg = TpuConfig(batch_size=batch, seq_len=128, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=16,
                     is_prefix_caching=False)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 500, size=8).tolist() for _ in range(batch)]
    sids = list(range(batch))

    def run(mode):
        spec = (SelfDraftProposer(3) if mode == "spec_k3"
                else SelfDraftProposer(7) if mode == "spec_k7" else None)
        eng = PagedEngineAdapter(app, speculation=spec)
        eng.add_requests(sids, prompts)
        base = dict(eng.host_stats)
        t0 = time.perf_counter()
        if mode == "step_many8":
            for _ in range(n_decode // 8):
                eng.step_many(8)
        elif spec is not None:
            eng.step_many(n_decode)  # token budget: exactly n_decode/row
        else:
            for _ in range(n_decode):
                eng.step()
        wall = time.perf_counter() - t0
        stats = {k: eng.host_stats[k] - base[k] for k in base}
        eng.release(sids)
        toks = n_decode * batch
        out = {
            "dispatches_per_100_tokens": round(
                100.0 * stats["dispatches"] / toks, 2),
            "blocking_syncs_per_100_tokens": round(
                100.0 * stats["blocking_fetches"] / toks, 2),
            "host_blocked_ms_per_token": round(
                stats["blocked_s"] * 1e3 / toks, 4),
            "wall_ms_per_token": round(wall * 1e3 / toks, 4),
        }
        if spec is not None:
            out["accept_rate"] = round(
                stats["spec_accepted_tokens"]
                / max(stats["spec_drafted_tokens"], 1), 4)
            out["verify_dispatches"] = stats["spec_verify_dispatches"]
            out["draft_dispatches"] = stats["spec_draft_dispatches"]
        return out

    modes = ("eager", "step_many8", "spec_k3", "spec_k7")
    for m in modes:
        run(m)                         # warm: compile every graph
    results = {m: run(m) for m in modes}
    ratio = (results["eager"]["dispatches_per_100_tokens"]
             / results["spec_k3"]["dispatches_per_100_tokens"])
    payload = {
        "metric": "spec_dispatches_eager_vs_selfdraft_k3",
        "value": round(ratio, 2),
        "unit": "x_fewer_dispatches_per_100_tokens_at_accept_1",
        "details": {
            **results,
            "decode_tokens_per_row": n_decode,
            "batch": batch,
            "proposer": "self-draft greedy (accept rate pinned at 1.0; "
                        "a real draft model trades accept rate for a "
                        "cheaper draft pass)",
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "spec-overhead")
    spec_sampled_main()


def spec_sampled_main(
        artifact_path="artifacts/bench_spec_sampled_r19.json"):
    """The sampled column of --spec-overhead plus the compressed-MLP
    roofline microbench (ISSUE 19). Part 1 re-runs the dispatch-economy
    measurement under SEEDED coupled sampling
    (``OnDeviceSamplingConfig(do_sample=True, stream_seed=...)``): the
    coupled verify accepts every self-draft just like greedy, so the
    2x-at-k=3 dispatch collapse must survive stochastic decode — and the
    artifact pins that the sampled speculative stream matched the sampled
    eager stream token-for-token during the run. Part 2 compares the AOT
    decode graphs of the tiny model dense vs ``mlp_low_rank=16``
    (XLA cost-analysis flops/bytes — the graph-report delta) and carries
    the analytic ``low_rank.compression_report`` roofline for the tiny
    shape and a 70B-class MLP."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import (
        OnDeviceSamplingConfig, TpuConfig)
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.modules import low_rank
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.speculation import \
        SelfDraftProposer
    from neuronx_distributed_inference_tpu.telemetry import observatory

    hf = _tiny_llama_hf()
    batch, n_decode = 2, 24

    def build(**extra):
        tcfg = TpuConfig(batch_size=batch, seq_len=128, dtype="float32",
                         enable_bucketing=True,
                         context_encoding_buckets=[16],
                         is_block_kv_layout=True, pa_block_size=16,
                         is_prefix_caching=False, **extra)
        app = PagedCausalLMApplication(
            None, LlamaInferenceConfig(tcfg, **hf), LlamaFamily)
        app.init_random_weights(seed=0).init_cache()
        return app

    app = build(on_device_sampling_config=OnDeviceSamplingConfig(
        do_sample=True, top_k=8, top_p=0.95, temperature=1.3,
        stream_seed=19))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 500, size=8).tolist() for _ in range(batch)]
    sids = list(range(batch))

    streams = {}

    def run(mode):
        spec = SelfDraftProposer(3) if mode == "spec_k3_sampled" else None
        eng = PagedEngineAdapter(app, speculation=spec)
        eng.add_requests(sids, prompts)
        base = dict(eng.host_stats)
        t0 = time.perf_counter()
        if spec is not None:
            eng.step_many(n_decode)  # token budget: exactly n_decode/row
        else:
            for _ in range(n_decode):
                eng.step()
        wall = time.perf_counter() - t0
        stats = {k: eng.host_stats[k] - base[k] for k in base}
        streams[mode] = {s: list(eng.seqs[s].tokens[len(prompts[s]):])
                         for s in sids}
        eng.release(sids)
        n_toks = n_decode * batch
        out = {
            "dispatches_per_100_tokens": round(
                100.0 * stats["dispatches"] / n_toks, 2),
            "wall_ms_per_token": round(wall * 1e3 / n_toks, 4),
        }
        if spec is not None:
            out["accept_rate"] = round(
                stats["spec_accepted_tokens"]
                / max(stats["spec_drafted_tokens"], 1), 4)
        return out

    modes = ("eager_sampled", "spec_k3_sampled")
    for m in modes:
        run(m)                         # warm: compile every graph
    results = {m: run(m) for m in modes}
    results["sampled_stream_bit_identical"] = (
        streams["eager_sampled"] == streams["spec_k3_sampled"])

    # -- compressed-MLP roofline: XLA decode-graph delta + analytic ------
    def decode_graph_cost(a):
        rep = observatory.analyze_app(a)
        decode = [g for g in rep["graphs"]       # the T=1 decode step
                  if g["kind"] == "paged" and g["bucket"].startswith("w1x")]
        return {"flops": sum(g["flops"] for g in decode),
                "bytes_accessed": sum(g["bytes_accessed"] for g in decode)}

    dense = decode_graph_cost(build())
    lowrank = decode_graph_cost(build(mlp_low_rank=16))
    graph_delta = {
        "dense": dense,
        "low_rank_r16": lowrank,
        "flops_ratio": round(lowrank["flops"] / max(dense["flops"], 1), 4),
        "bytes_ratio": round(
            lowrank["bytes_accessed"] / max(dense["bytes_accessed"], 1), 4),
    }
    payload = {
        "metric": "spec_dispatches_sampled_eager_vs_selfdraft_k3",
        "value": round(results["eager_sampled"]["dispatches_per_100_tokens"]
                       / results["spec_k3_sampled"]
                       ["dispatches_per_100_tokens"], 2),
        "unit": "x_fewer_dispatches_per_100_tokens_seeded_sampling",
        "details": {
            **results,
            "decode_tokens_per_row": n_decode,
            "batch": batch,
            "sampling": "top_k=8 top_p=0.95 temp=1.3 stream_seed=19 "
                        "(gumbel-coupled; README 'Sampled speculation & "
                        "compressed decode')",
            "low_rank_decode_graph_delta": graph_delta,
            "low_rank_analytic": {
                "tiny_r16": low_rank.compression_report(
                    hf["hidden_size"], hf["intermediate_size"],
                    hf["num_hidden_layers"], 16),
                "llama70b_r2048": low_rank.compression_report(
                    8192, 28672, 80, 2048, bytes_per_param=2.0),
            },
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "spec-sampled")


def ragged_overhead_main(artifact_path="artifacts/bench_ragged_r13.json"):
    """CPU-runnable ragged-dispatch microbench (ISSUE 13): drives the
    SAME staggered mixed workload — two short prompts decoding, then the
    8/120 skewed pair of bench_prefill admitted mid-decode, self-draft
    speculation k=3 throughout — through the two-phase paged adapter
    (at most one packed chunk dispatch, then one draft + one verify
    dispatch per engine step) and through ragged mode (ONE unified mixed
    dispatch per step, serving/ragged/). Reports dispatches and
    materialized (blocking-fetch) dispatches per engine step, plus
    prompt-token pad waste per ladder: the old ctx-sliced chunk ladder
    vs the unified ``ragged_row_buckets`` ladder (whose sub-ctx rungs
    let a trailing partial chunk pad to 8 instead of 16). Streams are
    asserted bit-identical across the modes, so the structural numbers
    compare the same tokens. One parseable JSON line + an artifact
    file, no TPU required."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.speculation import \
        SelfDraftProposer

    hf = _tiny_llama_hf()
    tcfg = TpuConfig(batch_size=4, seq_len=192, dtype="float32",
                     enable_bucketing=True, enable_2d_bucketing=True,
                     context_encoding_buckets=[16, 32, 64, 128],
                     is_block_kv_layout=True, pa_block_size=16,
                     pa_num_blocks=64, is_prefix_caching=False)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    rng = np.random.default_rng(0)
    warm = [rng.integers(1, 500, size=n).tolist() for n in (8, 12)]
    skew = [rng.integers(1, 500, size=n).tolist() for n in (8, 120)]
    want = 12                       # tokens per stream

    def run(ragged):
        eng = PagedEngineAdapter(app, speculation=SelfDraftProposer(3),
                                 prefill_chunk_tokens=16,
                                 prefill_budget_tokens=16, ragged=ragged)
        base = dict(eng.host_stats)
        got = {s: [] for s in range(4)}
        steps = 0

        def drive(ids, n):
            nonlocal steps
            while any(len(got[s]) < n for s in ids):
                for s, toks in eng.step().items():
                    toks = toks if isinstance(toks, list) else [toks]
                    got[s].extend(toks)
                steps += 1
                assert steps < 400, "mixed workload made no progress"

        t0 = time.perf_counter()
        eng.add_requests([0, 1], warm)
        drive((0, 1), 4)
        eng.add_requests([2, 3], skew)   # mid-decode: mixed load begins
        drive(range(4), want)
        wall_ms = (time.perf_counter() - t0) * 1e3
        stats = {k: eng.host_stats[k] - base.get(k, 0)
                 for k in eng.host_stats}
        eng.release(range(4))
        materialized = (stats["blocking_fetches"]
                        + stats["prefill_blocking_fetches"])
        out = {
            "engine_steps": steps,
            "dispatches": stats["dispatches"]
            + stats["prefill_dispatches"],
            "materialized_dispatches": materialized,
            "materialized_per_step": round(materialized / steps, 3),
            "dispatches_per_step": round(
                (stats["dispatches"] + stats["prefill_dispatches"])
                / steps, 3),
            "prefill_pad_waste": round(
                1.0 - stats["prefill_real_tokens"]
                / max(stats["prefill_padded_tokens"], 1), 4),
            "wall_ms": round(wall_ms, 2),
        }
        if ragged:
            out["ragged_pad_waste_total"] = round(
                1.0 - stats["ragged_real_tokens"]
                / max(stats["ragged_padded_tokens"], 1), 4)
        return out, got

    for mode in (False, True):
        run(mode)                      # warm: compile every graph
    two_phase, ref = run(False)
    ragged, got = run(True)
    assert all(got[s][:want] == ref[s][:want] for s in range(4)), \
        "ragged streams diverged from the two-phase path"
    payload = {
        "metric": "ragged_materialized_dispatches_per_engine_step",
        "value": ragged["materialized_per_step"],
        "unit": "materialized_dispatches_per_step_mixed_load",
        "details": {
            "two_phase": two_phase,
            "ragged": ragged,
            "pad_waste_ladders": {
                "prefill_chunk_ladder_two_phase":
                    two_phase["prefill_pad_waste"],
                "unified_ragged_ladder": ragged["prefill_pad_waste"],
            },
            "streams_bit_identical": True,
            "speculation": "self-draft k=3 (accept 1.0)",
            "prompt_lens": [len(p) for p in warm + skew],
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "ragged-overhead")


PERF_BASELINE_SCHEMA = "nxdi-perf-baseline-v1"


def perf_measure():
    """Measure the tracked serving-path proxy metrics (ISSUE 16's
    perf-drift gate): the ragged mixed-load structural counts
    (dispatches / materialized dispatches per engine step, ragged pad
    waste — the bench_ragged workload in ragged mode), the precompile
    plane's graph-ladder size and cold-start seconds
    (serving/warmup.py), and the SPMD golden set's total collective
    payload bytes. Every gated metric is a deterministic count or ratio
    on the tiny synthetic model — CPU-runnable, machine-independent;
    wall-clock style numbers are recorded but marked informational.
    Returns the flat ``{metric: value}`` dict the snapshot commits and
    ``scripts/check_perf_drift.py`` re-measures."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.speculation import \
        SelfDraftProposer
    from neuronx_distributed_inference_tpu.serving.warmup import precompile

    hf = _tiny_llama_hf()
    tcfg = TpuConfig(batch_size=4, seq_len=192, dtype="float32",
                     enable_bucketing=True, enable_2d_bucketing=True,
                     context_encoding_buckets=[16, 32, 64, 128],
                     is_block_kv_layout=True, pa_block_size=16,
                     pa_num_blocks=64, is_prefix_caching=False)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    # cold-start account FIRST (the graphs must not be warm yet): the
    # unified ladder's size is structural, its wall seconds are the
    # cold-start cost this machine paid (informational)
    warm_rep = precompile(app, chunk_tokens=16, declare_steady=False)

    rng = np.random.default_rng(0)
    warm = [rng.integers(1, 500, size=n).tolist() for n in (8, 12)]
    skew = [rng.integers(1, 500, size=n).tolist() for n in (8, 120)]
    want = 12
    eng = PagedEngineAdapter(app, speculation=SelfDraftProposer(3),
                             prefill_chunk_tokens=16,
                             prefill_budget_tokens=16, ragged=True)
    base = dict(eng.host_stats)
    got = {s: [] for s in range(4)}
    steps = 0

    def drive(ids, n):
        nonlocal steps
        while any(len(got[s]) < n for s in ids):
            for s, toks in eng.step().items():
                toks = toks if isinstance(toks, list) else [toks]
                got[s].extend(toks)
            steps += 1
            assert steps < 400, "mixed workload made no progress"

    eng.add_requests([0, 1], warm)
    drive((0, 1), 4)
    eng.add_requests([2, 3], skew)       # mid-decode: mixed load begins
    drive(range(4), want)
    stats = {k: eng.host_stats[k] - base.get(k, 0) for k in eng.host_stats}
    eng.release(range(4))
    materialized = (stats["blocking_fetches"]
                    + stats["prefill_blocking_fetches"])
    with open("artifacts/spmd_golden.json") as f:
        golden = json.load(f)
    golden_bytes = sum(c["bytes"] * c["count"]
                       for g in golden["graphs"].values()
                       for c in g["collectives"].values())
    migrations_per_drain, avoided = _measure_migration_proxies()
    lora_dps, lora_swap_bytes = _measure_lora_proxies()
    return {
        "dispatches_per_step": round(
            (stats["dispatches"] + stats["prefill_dispatches"]) / steps, 3),
        "materialized_per_step": round(materialized / steps, 3),
        "ragged_pad_waste": round(
            1.0 - stats["ragged_real_tokens"]
            / max(stats["ragged_padded_tokens"], 1), 4),
        "precompile_graphs": warm_rep["n_graphs"],
        "precompile_compiles": warm_rep["n_compiles"],
        "precompile_seconds": round(warm_rep["total_seconds"], 3),
        "golden_collective_bytes": golden_bytes,
        "migrations_per_drain": migrations_per_drain,
        "recompute_avoided_tokens": avoided,
        "lora_dispatches_per_step": lora_dps,
        "lora_swap_bytes": lora_swap_bytes,
    }


def _measure_migration_proxies():
    """Deterministic drain-by-migration mini-scenario (ISSUE 17's
    structural autoscale proxies): two spill-tier replicas, two
    mid-decode streams pinned onto one of them, then
    ``drain(mode="migrate")`` moves both. Returns
    ``(migrations per migrate-mode drain, KV tokens moved instead of
    recomputed)`` — both exact counts on the tiny model (every migrated
    fully-written block is block_size tokens the destination did NOT
    recompute-prefill), gated at 0.0 tolerance."""
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.engine import ServingEngine
    from neuronx_distributed_inference_tpu.serving.fleet import (
        EngineRouter, HostKVSpillTier)

    hf = _tiny_llama_hf()

    def make_engine():
        tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                         enable_bucketing=True,
                         context_encoding_buckets=[16],
                         is_block_kv_layout=True, pa_block_size=8,
                         is_prefix_caching=True)
        app = PagedCausalLMApplication(None,
                                       LlamaInferenceConfig(tcfg, **hf),
                                       LlamaFamily)
        app.init_random_weights(seed=0).init_cache()
        adapter = PagedEngineAdapter(
            app, kv_spill_tier=HostKVSpillTier(max_blocks=16))
        return ServingEngine(adapter, starvation_bound_s=1e9)

    router = EngineRouter({"r0": make_engine(), "r1": make_engine()})
    router.drain("r1")                   # pin both streams onto r0
    rng = np.random.default_rng(3)
    streams = [router.submit(rng.integers(1, 500, size=9).tolist(), 8)
               for _ in range(2)]
    router.undrain("r1")
    for _ in range(200):
        if all(s.n_tokens >= 5 for s in streams):
            break
        router.run_pass()
    moved = router.drain("r0", mode="migrate")
    router.run_until_drained()
    assert moved == 2 and all(s.finish_reason == "length" for s in streams)
    for rep in router.replicas.values():
        rep.engine.close()
    return (round(router.stats["migrations"]
                  / router.stats["migrate_drains"], 3),
            router.stats["migrated_kv_tokens"])


def _lora_bench_setup(n_adapters=4, seed=20):
    """Tiny LoRA-built paged app + a bounded adapter pool with
    ``n_adapters`` seeded synthetic adapters registered (more than the
    pool's device slots, so churn evicts) — shared by the perf-drift
    proxies and ``--lora-churn``."""
    from neuronx_distributed_inference_tpu.config import (LoraServingConfig,
                                                          TpuConfig)
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import LoraAdapterPool

    hf = _tiny_llama_hf()
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     pa_num_blocks=40, is_prefix_caching=True,
                     lora_config=LoraServingConfig(
                         max_loras=3, max_lora_rank=4,
                         target_modules=["q_proj", "v_proj"]))
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    pool = LoraAdapterPool(app, host_cache_adapters=2)
    lw = app.params["layers"]
    nprng = np.random.default_rng(seed)
    for i in range(n_adapters):
        arrays = {}
        for mod in app.spec.lora.target_modules:
            sa = lw[f"lora_A_{mod}"].shape       # (L, slots, in, r)
            sb = lw[f"lora_B_{mod}"].shape       # (L, slots, r, out)
            arrays[mod] = (
                (nprng.standard_normal((sa[0], sa[2], sa[3]))
                 * 0.05).astype(np.float32),
                (nprng.standard_normal((sb[0], sb[2], sb[3]))
                 * 0.05).astype(np.float32))
        pool.register_arrays(f"l{i}", arrays)
    return app, pool


def _drive_lora_mixed(app, pool, want=6):
    """One mixed-adapter ragged serve: three streams under DIFFERENT
    adapters (l0, l1, base model) through ONE engine adapter. Returns
    the host-stat deltas, engine steps, and the ragged pad-token
    counters — the structural evidence that multi-LoRA rides the
    one-dispatch-per-step unified path."""
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 500, size=n).tolist() for n in (9, 12, 7)]
    eng = PagedEngineAdapter(app, ragged=True, lora_pool=pool)
    base = dict(eng.host_stats)
    eng.add_requests([0, 1, 2], prompts,
                     meta=[{"adapter": "l0"}, {"adapter": "l1"}, None])
    got = {s: [] for s in range(3)}
    steps = 0
    while any(len(got[s]) < want for s in got):
        for s, toks in eng.step().items():
            got[s].extend(toks if isinstance(toks, list) else [toks])
        steps += 1
        assert steps < 200, "mixed-adapter workload made no progress"
    stats = {k: eng.host_stats[k] - base.get(k, 0) for k in eng.host_stats}
    eng.release(range(3))
    return stats, steps, got


def _lora_churn(pool, trace=("l0", "l0", "l2", "l2", "l0", "l1",
                             "l1", "l3", "l1", "l0")):
    """A skewed acquire/release trace over more adapters than device
    slots: repeated l0/l1/l2 acquires hit warm slots, the cold l2/l3
    arrivals force LRU evictions (device->host spills) and restores.
    All counts land in ``pool.stats`` — deterministic on the synthetic
    adapters."""
    for nm in trace:
        pool.acquire(nm)
        pool.release(nm)


def _measure_lora_proxies():
    """Deterministic multi-LoRA structural proxies (ISSUE 20's
    perf-drift extension): dispatches per engine step under a
    MIXED-adapter ragged serve (the one-dispatch pin — rows from
    different adapters plus base-model rows share every dispatch), and
    total swap H2D bytes after the serve + a skewed churn trace (exact
    byte count on the synthetic adapters; gated at 0.0)."""
    app, pool = _lora_bench_setup()
    stats, steps, _ = _drive_lora_mixed(app, pool)
    _lora_churn(pool)
    dispatches = stats["dispatches"] + stats["prefill_dispatches"]
    return (round(dispatches / steps, 3), int(pool.stats["swap_bytes"]))


def lora_churn_main(artifact_path="artifacts/bench_lora_r20.json"):
    """CPU-runnable multi-LoRA churn microbench (ISSUE 20): a
    mixed-adapter ragged serve (adapters l0/l1 + a base-model row in one
    engine) followed by a skewed adapter churn over MORE adapters than
    device slots, against the bounded pool (serving/lora_pool.py).
    Reports residency hit-rate, swap H2D bytes/latency, eviction +
    spill/restore counts, the dispatches-per-step pin under mixed
    adapters, ragged pad-waste, and the AOT bytes/flops delta of the
    lora-augmented unified graph vs the plain ragged graph
    (telemetry/observatory.py)."""
    force_cpu_devices(1)
    from neuronx_distributed_inference_tpu.telemetry import observatory

    app, pool = _lora_bench_setup()
    stats, steps, got = _drive_lora_mixed(app, pool)
    serve_stats = dict(pool.stats)
    _lora_churn(pool)
    ps = pool.stats
    dispatches = stats["dispatches"] + stats["prefill_dispatches"]
    pad_waste = round(1.0 - stats["ragged_real_tokens"]
                      / max(stats["ragged_padded_tokens"], 1), 4)
    hit_rate = round(ps["hits"] / max(ps["hits"] + ps["misses"], 1), 4)
    # AOT graph delta: the lora-augmented unified dispatch vs the plain
    # ragged graph on the SAME app (the per-row (A, B) gather + delta
    # einsum is the entire difference)
    graphs = {}
    for kind, bucket, build in observatory._graph_entries(app):
        if kind in ("ragged", "ragged_lora"):
            fn, args, kwargs = build()
            with app._mesh_ctx():
                compiled = fn.lower(*args, **kwargs).compile()
            flops, bytes_acc = observatory._cost(compiled)
            graphs[kind] = {"bucket": bucket, "flops": flops,
                            "bytes_accessed": bytes_acc}
    delta = {
        "flops": graphs["ragged_lora"]["flops"] - graphs["ragged"]["flops"],
        "bytes_accessed": (graphs["ragged_lora"]["bytes_accessed"]
                           - graphs["ragged"]["bytes_accessed"]),
    }
    payload = {
        "metric": "lora_dispatches_per_step_mixed_adapters",
        "value": round(dispatches / steps, 3),
        "unit": "dispatches_per_engine_step_mixed_adapter_load",
        "details": {
            "engine_steps": steps,
            "dispatches": dispatches,
            "tokens": sum(len(v) for v in got.values()),
            "streams": {"l0": 1, "l1": 1, "base": 1},
            "ragged_pad_waste": pad_waste,
            "residency_hit_rate": hit_rate,
            "swap_bytes": ps["swap_bytes"],
            "swap_seconds": round(ps["swap_s"], 4),
            "swaps": ps["swaps"],
            "cold_loads": ps["cold_loads"],
            "restores": ps["restores"],
            "spills": ps["spills"],
            "evictions": ps["evictions"],
            "host_evictions": ps["host_evictions"],
            "serve_only": {k: serve_stats[k]
                           for k in ("swaps", "swap_bytes", "hits",
                                     "misses")},
            "pool": {"device_slots": pool.n_slots,
                     "registered": len(pool.names),
                     "host_cache_adapters": pool.max_host},
            "graphs": graphs,
            "lora_graph_delta": delta,
            "model": "llama-tiny 2L/64h (synthetic fp32), rank-4 "
                     "adapters on q_proj/v_proj",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "lora-churn")
    return 0


def perf_snapshot_main(artifact_path="artifacts/perf_baseline_r16.json"):
    """Write the committed perf-drift baseline (ISSUE 16): one
    ``nxdi-perf-baseline-v1`` artifact holding the tracked proxy metrics
    from :func:`perf_measure` plus the per-metric drift tolerances the
    gate enforces. ``scripts/check_perf_drift.py`` re-measures and
    diffs; the static ``perf-drift`` nxdi-lint pass keeps the committed
    artifact well-formed and its golden-bytes pin in sync with
    ``artifacts/spmd_golden.json``. Re-run THIS entry point to
    re-baseline deliberately (the README section documents the ritual)."""
    metrics = perf_measure()
    payload = {
        "schema": PERF_BASELINE_SCHEMA,
        "metric": "perf_snapshot_dispatches_per_step",
        "value": metrics["dispatches_per_step"],
        "unit": "dispatches_per_engine_step_mixed_load",
        "metrics": metrics,
        # symmetric relative tolerances (improvements red too — re-earn
        # the baseline on purpose, like the SPMD golden); None = recorded
        # but not gated (machine-dependent wall clock)
        "tolerances": {
            "dispatches_per_step": 0.10,
            "materialized_per_step": 0.10,
            "ragged_pad_waste": 0.25,
            "precompile_graphs": 0.0,
            "precompile_compiles": None,
            "precompile_seconds": None,
            "golden_collective_bytes": 0.0,
            "migrations_per_drain": 0.0,
            "recompute_avoided_tokens": 0.0,
            "lora_dispatches_per_step": 0.0,
            "lora_swap_bytes": 0.0,
        },
        "details": {
            "workload": "bench_ragged mixed load (self-draft k=3, "
                        "skewed 8/120 admit mid-decode), ragged mode",
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "perf-snapshot")


def serving_load_main(artifact_path="artifacts/bench_serving_r08.json"):
    """CPU-runnable closed-loop serving-load microbench (ISSUE 6): drives
    the multi-tenant ServingEngine over the paged adapter with a 2x
    oversubscribed three-tenant arrival trace on the tiny synthetic model
    and reports client-observed TTFT/TPOT p50/p99, the weighted fairness
    ratio (per-tenant tokens/s normalized by weight, min/max across
    tenants — 1.0 is perfectly weight-proportional), and preemption /
    requeue counts. One parseable JSON line + an artifact file; no TPU
    required (reference yardstick for WHAT a TPU serving stack reports:
    the Gemma-on-Cloud-TPU comparison, PAPERS.md arxiv 2605.25645)."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.engine import ServingEngine

    hf = _tiny_llama_hf()
    batch, max_new, prompt_len = 8, 16, 10
    weights = {"a": 1.0, "b": 1.0, "c": 2.0}
    # closed loop at 2x oversubscription: each tenant keeps twice its
    # weighted slot share in flight and replaces a finished request with
    # the next from its quota (quotas weight-proportional, so every
    # tenant's trace spans the same steady-state window)
    slot_share = {t: int(batch * w / sum(weights.values()))
                  for t, w in weights.items()}
    outstanding_target = {t: 2 * s for t, s in slot_share.items()}
    quota = {t: 4 * s for t, s in slot_share.items()}
    # one slot-share worth of each tenant's quota is held back and injected
    # as a single high-priority burst at the halfway mark — it arrives
    # while the batch is FULL, so it exercises scheduler-driven preemption
    # + requeue (a closed loop alone admits high-priority work through
    # freed slots and never needs to evict); per-tenant totals stay
    # weight-proportional so the fairness measurement is undisturbed
    reserve = dict(slot_share)
    quota_normal = {t: quota[t] - reserve[t] for t in weights}

    tcfg = TpuConfig(batch_size=batch, seq_len=128, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=16,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    adapter = PagedEngineAdapter(app, prefill_budget_tokens=32)
    eng = ServingEngine(adapter, tenant_weights=weights,
                        starvation_bound_s=30.0)

    rng = np.random.default_rng(0)
    records = []          # [tenant, stream, t_submit, t_first, t_done]
    submitted = {t: 0 for t in weights}

    def submit_one(t, now, prio=0):
        prompt = rng.integers(1, 500, size=prompt_len).tolist()
        stream = eng.submit(prompt, max_new, tenant=t, priority=prio)
        records.append([t, stream, now, None, None])
        submitted[t] += 1

    def top_up(now):
        for t in weights:
            live = sum(1 for r in records
                       if r[0] == t and r[4] is None)
            while (live < outstanding_target[t]
                   and submitted[t] < quota_normal[t]):
                submit_one(t, now)
                live += 1

    total = sum(quota.values())
    burst_done = False
    t_start = time.perf_counter()
    while True:
        now = time.perf_counter()
        top_up(now)
        if not burst_done and eng.stats["completed"] >= total // 2:
            burst_done = True
            for t in weights:
                for _ in range(reserve[t]):
                    submit_one(t, now, prio=5)
        if not eng.has_work:
            break
        eng.run_pass()
        now = time.perf_counter()
        for rec in records:
            if rec[3] is None and rec[1].tokens:
                rec[3] = now
            if rec[4] is None and rec[1].finished:
                rec[4] = now
    wall = time.perf_counter() - t_start

    assert all(r[1].finish_reason == "length" for r in records)
    ttft = np.asarray([r[3] - r[2] for r in records])
    tpot = np.asarray([(r[4] - r[3]) / (max_new - 1) for r in records])
    per_tenant_tok_s = {
        t: sum(len(r[1].tokens) for r in records if r[0] == t) / wall
        for t in weights}
    norm = {t: per_tenant_tok_s[t] / weights[t] for t in weights}
    fairness = min(norm.values()) / max(norm.values())

    pct = lambda a, q: float(np.percentile(a, q) * 1e3)  # noqa: E731
    payload = {
        "metric": "serving_load_weighted_fairness",
        "value": round(fairness, 4),
        "unit": "min_over_max_weight_normalized_tok_s",
        "details": {
            "requests": len(records),
            "oversubscription": 2.0,
            "tenant_weights": weights,
            "per_tenant_tok_s": {t: round(v, 2)
                                 for t, v in per_tenant_tok_s.items()},
            "ttft_ms": {"p50": round(pct(ttft, 50), 2),
                        "p99": round(pct(ttft, 99), 2)},
            "tpot_ms": {"p50": round(pct(tpot, 50), 2),
                        "p99": round(pct(tpot, 99), 2)},
            "preempt_requeues": eng.stats["preempt_requeues"],
            "priority_preemptions": eng.stats["priority_preemptions"],
            "completed": eng.stats["completed"],
            "wall_s": round(wall, 2),
            "batch": batch,
            "max_new_tokens": max_new,
            "prefill_budget_tokens": 32,
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    print(json.dumps(payload))
    try:
        os.makedirs(os.path.dirname(artifact_path), exist_ok=True)
        with open(artifact_path, "w") as f:
            json.dump(payload, f, indent=1)
    except OSError as e:  # pragma: no cover - diagnostics only
        print(f"serving-load artifact write failed: {e}", file=sys.stderr)


def fleet_load_main(artifact_path="artifacts/bench_fleet_r11.json"):
    """CPU-runnable closed-loop fleet microbench (ISSUE 11): two
    ServingEngine replicas (same synthetic weights) behind the
    EngineRouter, sharing one host-RAM KV spill tier, under a two-wave
    prefix-grouped workload on an undersized block pool — so
    prefix-affinity routing, LRU spill and tier restore all actually
    fire. Reports N-replica routing fairness (min/max requests routed
    per replica), the affinity hit-rate (share of routing decisions that
    found a warm replica), spill/restore/evict counts from the shared
    tier, and client-observed TTFT/TPOT p50/p99 (reference yardstick for
    WHAT a fleet reports: the Gemma-on-Cloud-TPU serving comparison,
    PAPERS.md arxiv 2605.25645). One parseable JSON line + an artifact
    file; no TPU required."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.engine import ServingEngine
    from neuronx_distributed_inference_tpu.serving.fleet import (
        EngineRouter, HostKVSpillTier)

    hf = _tiny_llama_hf()
    batch, max_new, n_groups = 4, 8, 6
    prefix_len, suffix_len = 32, 4               # 2 full 16-token blocks

    def make_engine():
        # pa_num_blocks undersized (12 usable ~= the full-batch working set)
        # so steady-state admissions actually evict LRU residents — the
        # spill tier's reason to exist
        tcfg = TpuConfig(batch_size=batch, seq_len=128, dtype="float32",
                         enable_bucketing=True,
                         context_encoding_buckets=[16, 64],
                         is_block_kv_layout=True, pa_block_size=16,
                         pa_num_blocks=12, is_prefix_caching=True)
        app = PagedCausalLMApplication(None,
                                       LlamaInferenceConfig(tcfg, **hf),
                                       LlamaFamily)
        app.init_random_weights(seed=0).init_cache()
        adapter = PagedEngineAdapter(app, kv_spill_tier=tier)
        return ServingEngine(adapter, starvation_bound_s=30.0)

    # ONE shared tier: content-hash keying makes cross-replica sharing
    # safe (same weights => same payload per chain hash), so warmth
    # spilled by one replica is restorable by the other
    tier = HostKVSpillTier(max_blocks=64)
    router = EngineRouter({"r0": make_engine(), "r1": make_engine()})

    rng = np.random.default_rng(0)
    prefixes = [rng.integers(1, 500, size=prefix_len).tolist()
                for _ in range(n_groups)]
    records = []

    def submit(prompt):
        s = router.submit(prompt, max_new)
        records.append({
            "stream": s,
            "replica": router._requests[s.request_id].replica,
            "t_submit": time.perf_counter(), "t_first": None,
            "t_done": None})

    def drain():
        while router.has_work:
            router.run_pass()
            now = time.perf_counter()
            for r in records:
                if r["t_first"] is None and r["stream"].n_tokens:
                    r["t_first"] = now
                if r["t_done"] is None and r["stream"].finished:
                    r["t_done"] = now

    t_start = time.perf_counter()
    for wave in range(2):
        # two requests per prefix group per wave, with MORE distinct
        # prefix groups than the undersized pool can keep resident: the
        # oversubscribed wave churns the prefix cache (LRU evictions →
        # spills), and wave 2 re-presents every prefix so affinity
        # routing and tier restores are exercised, not measured at zero
        for g, prefix in enumerate(prefixes):
            for j in range(2):
                submit(prefix + rng.integers(1, 500,
                                             size=suffix_len).tolist())
        drain()
    wall = time.perf_counter() - t_start

    assert all(r["stream"].finish_reason == "length" for r in records)
    per_replica = {}
    for r in records:
        per_replica[r["replica"]] = per_replica.get(r["replica"], 0) + 1
    fairness = (min(per_replica.values()) / max(per_replica.values())
                if len(per_replica) > 1 else 0.0)
    routed = router.stats["routed"]
    hit_rate = router.stats["affinity_warm"] / max(routed, 1)
    ttft = np.asarray([r["t_first"] - r["t_submit"] for r in records])
    tpot = np.asarray([(r["t_done"] - r["t_first"]) / (max_new - 1)
                       for r in records])
    pct = lambda a, q: float(np.percentile(a, q) * 1e3)  # noqa: E731
    payload = {
        "metric": "fleet_load_affinity_hit_rate",
        "value": round(hit_rate, 4),
        "unit": "warm_routes_over_routes_2_replicas",
        "details": {
            "requests": len(records),
            "replicas": 2,
            "routed_per_replica": per_replica,
            "routing_fairness_min_over_max": round(fairness, 4),
            "affinity": {"warm": router.stats["affinity_warm"],
                         "cold": router.stats["affinity_cold"]},
            "kv_tier": {k: tier.stats[k] for k in
                        ("spilled", "restored", "evicted", "hits",
                         "misses", "spill_errors")},
            "kv_tier_resident_blocks": len(tier),
            "ttft_ms": {"p50": round(pct(ttft, 50), 2),
                        "p99": round(pct(ttft, 99), 2)},
            "tpot_ms": {"p50": round(pct(tpot, 50), 2),
                        "p99": round(pct(tpot, 99), 2)},
            "preempt_requeues": sum(
                rep.engine.stats["preempt_requeues"]
                for rep in router.replicas.values()),
            "wall_s": round(wall, 2),
            "batch_per_replica": batch,
            "pa_num_blocks": 12,
            "prefix_groups": n_groups,
            "max_new_tokens": max_new,
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "fleet-load")


def autoscale_report_main(
        artifact_path="artifacts/bench_autoscale_r17.json"):
    """CPU-runnable closed-loop autoscaler report (ISSUE 17): replay a
    seeded diurnal-ramp workload (serving/fleet/loadgen.py) against an
    elastic fleet on a VIRTUAL clock — the FleetAutoscaler (attached to
    the EngineRouter, consulted once per pass) must scale up on the
    ramp's front slope with a replica that PRECOMPILED to zero compiles
    against the shared persistent compilation cache, and scale back
    down on the far slope by drain-by-migration (running streams move
    with their KV). Reports the scale timeline, migrated-stream count
    and virtual-clock TTFT/TPOT p50/p99; asserts >= 1 scale-up, >= 1
    scale-down, hysteresis (opposite actions separated by >= the
    cooldown) and n_compiles == 0 on every admitted replica. One
    parseable JSON line + an artifact file; no TPU required."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.engine import ServingEngine
    from neuronx_distributed_inference_tpu.serving.fleet import (
        EngineRouter, FleetAutoscaler, HostKVSpillTier, diurnal_ramp)
    from neuronx_distributed_inference_tpu.serving.warmup import precompile

    hf = _tiny_llama_hf()
    max_new = 6

    def make_app():
        tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                         enable_bucketing=True,
                         context_encoding_buckets=[16],
                         is_block_kv_layout=True, pa_block_size=8,
                         is_prefix_caching=True)
        app = PagedCausalLMApplication(None,
                                       LlamaInferenceConfig(tcfg, **hf),
                                       LlamaFamily)
        app.init_random_weights(seed=0).init_cache()
        return app

    def make_engine():
        return ServingEngine(
            PagedEngineAdapter(make_app(),
                               kv_spill_tier=HostKVSpillTier(
                                   max_blocks=32)),
            starvation_bound_s=1e9)

    # the fleet precompile plane (ISSUE 16) warms the SHARED persistent
    # cache once, up front — the precompile-first admission gate then
    # requires every spawned replica to report n_compiles == 0 off it
    t_warm = time.perf_counter()
    warm_report = precompile(make_app())
    warm_s = time.perf_counter() - t_warm

    clock = [0.0]
    tick = 0.5
    auto = FleetAutoscaler(
        make_engine, min_replicas=1, max_replicas=3,
        queue_enter=4.0, queue_exit=0.5,
        burn_enter=1.0, burn_exit=0.25,
        headroom_enter_slots=0, headroom_exit_slots=2,
        min_hold_s=1.0, cooldown_s=5.0, now_fn=lambda: clock[0])
    router = EngineRouter({"r0": make_engine()}, autoscaler=auto)

    arrivals = diurnal_ramp(duration_s=40.0, base_rate=0.3,
                            peak_rate=5.0, vocab=500, prompt_len=(5, 10),
                            max_new_tokens=max_new, seed=0)
    records = []
    replica_counts = []
    i = 0
    t_start = time.perf_counter()
    while i < len(arrivals) or router.has_work or auto._retiring:
        clock[0] += tick
        while i < len(arrivals) and arrivals[i].t <= clock[0]:
            s = router.submit(list(arrivals[i].prompt),
                              arrivals[i].max_new_tokens,
                              tenant=arrivals[i].tenant)
            records.append({"stream": s, "t_submit": arrivals[i].t,
                            "t_first": None, "t_done": None})
            i += 1
        # ONE fleet pass per virtual half-second: a deliberately tight
        # per-replica token budget, so the ramp's peak genuinely
        # oversubscribes one replica and the controller must act
        router.run_pass()
        for r in records:
            if r["t_first"] is None and r["stream"].n_tokens:
                r["t_first"] = clock[0]
            if r["t_done"] is None and r["stream"].finished:
                r["t_done"] = clock[0]
        replica_counts.append(sum(
            1 for rep in router.replicas.values()
            if rep.state in ("healthy", "draining")))
        assert clock[0] < 3600.0, "autoscale workload wedged"
    wall = time.perf_counter() - t_start

    assert all(r["stream"].finish_reason == "length" for r in records)
    ups = [h for h in auto.history if h["action"] == "scale_up"]
    downs = [h for h in auto.history if h["action"] == "scale_down"]
    assert ups, "diurnal ramp produced no scale-up"
    assert downs, "diurnal ramp produced no scale-down"
    assert all(h["n_compiles"] == 0 for h in ups), \
        "a scale-up replica compiled at admission (cache not shared?)"
    # hysteresis: consecutive OPPOSITE actions >= cooldown apart
    actions = [h for h in auto.history
               if h["action"] in ("scale_up", "scale_down")]
    min_flip_gap = min(
        (b["t"] - a["t"] for a, b in zip(actions, actions[1:])
         if a["action"] != b["action"]), default=float("inf"))
    assert min_flip_gap >= auto.cooldown_s, \
        f"hysteresis violated: opposite actions {min_flip_gap}s apart"
    ttft = np.asarray([r["t_first"] - r["t_submit"] for r in records])
    tpot = np.asarray([(r["t_done"] - r["t_first"]) / (max_new - 1)
                       for r in records])
    pct = lambda a, q: float(np.percentile(a, q) * 1e3)  # noqa: E731
    payload = {
        "metric": "autoscale_scale_actions",
        "value": len(actions),
        "unit": "scale_actions_diurnal_ramp_virtual_40s",
        "details": {
            "requests": len(records),
            "scale_ups": len(ups),
            "scale_downs": len(downs),
            "timeline": auto.history,
            "min_opposite_action_gap_s": (
                None if min_flip_gap == float("inf")
                else round(min_flip_gap, 2)),
            "cooldown_s": auto.cooldown_s,
            "replicas_peak": max(replica_counts),
            "replicas_final": replica_counts[-1],
            "migrated_streams": router.stats["migrations"],
            "migrated_kv_tokens": router.stats["migrated_kv_tokens"],
            "reaped": auto.stats["reaped"],
            "autoscaler_stats": dict(auto.stats),
            "precompile": {"n_graphs": warm_report["n_graphs"],
                           "warm_wall_s": round(warm_s, 2)},
            "ttft_virtual_ms": {"p50": round(pct(ttft, 50), 1),
                                "p99": round(pct(ttft, 99), 1)},
            "tpot_virtual_ms": {"p50": round(pct(tpot, 50), 1),
                                "p99": round(pct(tpot, 99), 1)},
            "virtual_horizon_s": round(clock[0], 1),
            "wall_s": round(wall, 2),
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    for rep in router.replicas.values():
        if not getattr(rep.engine, "closed", False):
            rep.engine.close()
    _emit_report_artifact(payload, artifact_path, "autoscale-report")


def slo_report_main(artifact_path="artifacts/bench_slo_r14.json"):
    """CPU-runnable SLO-plane report (ISSUE 14): a two-tenant
    closed-loop run on the tiny synthetic paged engine with an
    SLOTracker attached — per-tenant TTFT / TPOT / queue-wait p50/p99
    over the rolling windows, attainment and burn rate against a
    deliberately tight policy (so the burn math exercises non-zero
    violations on any host), and the advisory degradation hint. One
    parseable JSON line + an artifact file; no TPU required. This is
    the answer layer over the histograms the engine already records:
    the numbers the Gemma-on-Cloud-TPU serving comparison (PAPERS.md,
    arxiv 2605.25645) frames as the serving yardstick."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.engine import ServingEngine
    from neuronx_distributed_inference_tpu.telemetry.slo import (SLOPolicy,
                                                                 SLOTracker)

    hf = _tiny_llama_hf()
    tcfg = TpuConfig(batch_size=4, seq_len=128, dtype="float32",
                     enable_bucketing=True,
                     context_encoding_buckets=[16, 64],
                     is_block_kv_layout=True, pa_block_size=16,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    # tight targets: on a CPU host the decode step is slower than 2 ms,
    # so tpot burns by construction — the report demonstrates real burn
    # math, not a wall of zeros (ttft/queue_wait stay generous)
    policy = SLOPolicy(targets={"ttft": 2.0, "tpot": 0.002,
                                "queue_wait": 2.0}, objective=0.9)
    tracker = SLOTracker(policy)
    eng = ServingEngine(PagedEngineAdapter(app), starvation_bound_s=30.0,
                        tenant_weights={"gold": 2.0, "bronze": 1.0},
                        slo=tracker)
    rng = np.random.default_rng(0)
    max_new = 8
    streams = []
    t_start = time.perf_counter()
    for wave in range(2):
        # 2x oversubscription per wave so queue wait is non-zero
        for i in range(8):
            tenant = "gold" if i % 2 == 0 else "bronze"
            streams.append(eng.submit(
                rng.integers(1, 500, size=12).tolist(), max_new,
                tenant=tenant))
        eng.run_until_drained()
    wall = time.perf_counter() - t_start
    assert all(s.finish_reason == "length" for s in streams)

    report = tracker.report()
    hint = report["hint"]
    burns = [sig.get("burn_rate", {}).get("long", 0.0)
             for ten in report["tenants"].values() for sig in ten.values()]
    payload = {
        "metric": "slo_report_max_burn_rate_long",
        "value": round(max(burns), 4) if burns else 0.0,
        "unit": "violation_fraction_over_error_budget",
        "details": {
            "schema": report["schema"],
            "requests": len(streams),
            "tenants": report["tenants"],
            "policy": report["policy"],
            "degradation_hint": hint,
            "wall_s": round(wall, 2),
            "max_new_tokens": max_new,
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "slo-report")


def chaos_report_main(artifact_path="artifacts/bench_chaos_r15.json"):
    """CPU-runnable chaos campaign (ISSUE 15): sweep EVERY registered
    fault point — single-shot and repeated-Nth schedules — against a
    seeded staggered mixed fleet workload (chunked prefill + decode +
    speculative verify + ragged unified dispatch + KV spill tier +
    disaggregated handoff + replica failover on three tiny same-weights
    engines), asserting the global invariants after every heal: streams
    bit-identical to the fault-free golden (requeues included), no
    stream lost, exact free-pool accounting, zero unwritten-block
    leaks, and every armed point actually fired. One parseable JSON
    line + the per-point outcome artifact; no TPU required. rc 1 when
    any cell is red — a chaos regression IS a regression."""
    force_cpu_devices(1)

    from neuronx_distributed_inference_tpu.config import (LoraServingConfig,
                                                          TpuConfig)
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.resilience.chaos import \
        ChaosCampaign

    hf = _tiny_llama_hf()

    def make_app():
        # replicas of ONE model: same weights seed on every app.
        # LoRA-built so the workload's adapter-churn phase traverses the
        # adapter_swap / adapter_spill fault points (slots start zero —
        # base streams are bit-identical to a no-LoRA build)
        tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                         enable_bucketing=True,
                         context_encoding_buckets=[16],
                         is_block_kv_layout=True, pa_block_size=8,
                         is_prefix_caching=True,
                         lora_config=LoraServingConfig(
                             max_loras=3, max_lora_rank=4,
                             target_modules=["q_proj", "v_proj"]))
        app = PagedCausalLMApplication(None,
                                       LlamaInferenceConfig(tcfg, **hf),
                                       LlamaFamily)
        app.init_random_weights(seed=7).init_cache()
        return app

    campaign = ChaosCampaign([make_app() for _ in range(3)], seed=0)
    report = campaign.run()
    failed = [c for c in report["cells"] if not c["ok"]]
    payload = {
        "metric": "chaos_failed_cells",
        "value": len(failed),
        "unit": f"red_cells_of_{len(report['cells'])}_point_schedules",
        "details": {
            "schema": report["schema"],
            "ok": report["ok"],
            "seed": report["seed"],
            "points": report["points"],
            "golden": report["golden"],
            "cells": report["cells"],
            "wall_s": report["wall_s"],
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
        },
    }
    _emit_report_artifact(payload, artifact_path, "chaos-report")
    return 0 if report["ok"] else 1


def graph_report_main(artifact_path="artifacts/graph_report_r08.json"):
    """CPU-runnable compiled-graph observatory report (ISSUE 7): AOT
    ``.lower().compile()`` of every bucket-ladder graph of the tiny
    synthetic models (paged + contiguous), harvesting XLA's static
    cost/memory analysis — per-bucket flops, bytes accessed, peak memory,
    compile wall time, and a static roofline estimate under the assumed
    chip constants. One parseable JSON line + an artifact file, no TPU
    required: this is the hardware-free evidence trail for cold-start
    (compile-seconds) and graph-size regressions, and the baseline for
    re-earning the frozen kernel-admission constants (ROADMAP item 5)."""
    from neuronx_distributed_inference_tpu.telemetry import observatory
    force_cpu_devices(1)

    reports = _observatory_reports(mesh=False, label="graph report")
    total_compile = round(sum(r["totals"]["compile_seconds"]
                              for r in reports.values()), 4)
    payload = {
        "metric": "graph_report_compile_seconds_total",
        "value": total_compile,
        "unit": "s_aot_compile_all_bucket_graphs",
        "details": {
            "schema": observatory.GRAPH_REPORT_SCHEMA,
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
            "apps": reports,
        },
    }
    _emit_report_artifact(payload, artifact_path, "graph-report")


def lint_report_main(artifact_path="artifacts/lint_report_r10.json"):
    """Static-analysis report (ISSUE 10): run every ``nxdi_lint`` pass
    in-process (no jax, sub-second) and commit the ``nxdi-lint-v1``
    artifact, so lint findings trend across rounds exactly like bench
    numbers — a finding count going 0 -> N between rounds is a
    regression trajectory, not a folklore code-review memory. One
    parseable JSON line + the artifact file."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import nxdi_lint
    report = nxdi_lint.run()
    # the artifact IS the driver's --json output (one schema at this
    # path: nxdi-lint-v1), the heartbeat line is bench-parseable
    try:
        nxdi_lint.write_artifact(report, artifact_path)
    except OSError as e:  # pragma: no cover - defensive
        print(f"lint-report artifact write failed: {e}", file=sys.stderr)
    print(json.dumps({
        "metric": "lint_findings_total",
        "value": len(report.findings),
        "unit": "findings_all_passes",
        "details": {"schema": "nxdi-lint-v1", "artifact": artifact_path,
                    "files": len(report.files),
                    "suppressed": len(report.suppressed)},
    }))
    return 0 if not report.findings else 1


def _observatory_reports(mesh, label, quantized=False):
    """Build the tiny paged + cb serving apps (on the dp2 x tp2 CPU mesh
    when ``mesh``) and run the compiled-graph observatory over both —
    the shared core of ``--graph-report`` and ``--sharding-report``. The
    heartbeat line carries the gauge totals (compile seconds, collective
    bytes) on stderr. With
    ``quantized`` (mesh only) a third app — the same cb config with
    ``CollectiveConfig(dtype="int8")`` — is analyzed as ``cb_int8`` so
    the report carries the quantized-collective comm-roofline delta."""
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.config import (CollectiveConfig,
                                                          TpuConfig)
    from neuronx_distributed_inference_tpu.models.application import (
        CausalLMApplication, PagedCausalLMApplication)
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.telemetry import observatory

    hf = _tiny_llama_hf()
    mesh_fields = dict(tp_degree=4, attention_dp_degree=2) if mesh else {}

    def analyze(cls, tcfg):
        # the application derives its mesh from tcfg's degree fields
        app = cls(None, LlamaInferenceConfig(tcfg, **hf), LlamaFamily)
        app.init_random_weights(seed=0).init_cache()
        return observatory.analyze_app(app)

    def cb_tcfg(**extra):
        return TpuConfig(
            batch_size=2, seq_len=128, dtype="float32",
            enable_bucketing=True, context_encoding_buckets=[16, 64],
            is_continuous_batching=True, decode_chunk_tokens=8,
            **mesh_fields, **extra)

    reg = telemetry.enable()
    try:
        reports = {
            "paged": analyze(PagedCausalLMApplication, TpuConfig(
                batch_size=2, seq_len=128, dtype="float32",
                enable_bucketing=True, context_encoding_buckets=[16, 64],
                is_block_kv_layout=True, pa_block_size=16,
                is_prefix_caching=True,
                **(dict(decode_chunk_tokens=4, **mesh_fields)
                   if mesh else {}))),
            "cb": analyze(CausalLMApplication, cb_tcfg()),
        }
        if quantized and mesh:
            reports["cb_int8"] = analyze(CausalLMApplication, cb_tcfg(
                collective_config=CollectiveConfig(dtype="int8")))
        line = reg.stats_line()
        if line:
            print(f"[bench telemetry | {label}] {line}", file=sys.stderr)
    finally:
        telemetry.disable()
    return reports


def _emit_report_artifact(payload, artifact_path, label):
    print(json.dumps(payload))
    try:
        os.makedirs(os.path.dirname(artifact_path), exist_ok=True)
        with open(artifact_path, "w") as f:
            json.dump(payload, f, indent=1)
    except OSError as e:  # pragma: no cover - diagnostics only
        print(f"{label} artifact write failed: {e}", file=sys.stderr)


def _project_70b_v5e32():
    """Analytic decode roofline for Llama-70B on a v5e-32 pod slice as
    dp4 x tp8 with dp crossing the DCN boundary (``parallel.mesh
    .DP_OVER_DCN``) — the scale-out shape the quantized collectives
    target. Pure math under the same v5e peaks the observatory prices
    with (utils/device.py DEVICE_PEAKS), so the projection
    line trends with the measured census in one artifact. The comm leg
    is the per-decode-step row-parallel exchange (o_proj + down_proj per
    layer), priced fp32 vs int8+fp32-scales; dp carries ZERO per-step
    decode collectives — that independence is exactly why dp is the axis
    that may leave the slice."""
    peaks = device_peaks(V5E)
    peak_tflops, hbm_gbps = peaks.bf16_tflops, peaks.hbm_gbps
    ici_gbps, dcn_gbps = peaks.ici_gbps, peaks.dcn_gbps
    # Llama-70B geometry
    L, H, I, V = 80, 8192, 28672, 128256
    n_kv, hd = 8, 128
    tp, dp, batch = 8, 4, 8          # per-replica decode batch
    params = (L * (2 * H * H + 2 * H * n_kv * hd + 3 * H * I)
              + 2 * V * H)
    # memory leg: every weight byte streams from HBM once per step
    wbytes_bf16 = params * 2 / tp
    t_mem = wbytes_bf16 / (hbm_gbps * 1e9)
    # compute leg: 2 flops/param/token, tp-sharded
    t_comp = 2.0 * params * batch / tp / (peak_tflops * 1e12)
    # comm leg: 2 row-parallel all-reduces of (batch, 1, H) per layer,
    # ring wire factor 2(g-1)/g over the tp=8 ICI axis
    elems = 2 * L * batch * H
    factor = 2.0 * (tp - 1) / tp
    wire_f32 = factor * elems * 4
    # int8 payload + blockwise fp32 scales (1 scale per 32 elements)
    wire_int8 = factor * elems * (1 + 4 / 32)
    t_comm_f32 = wire_f32 / (ici_gbps * 1e9)
    t_comm_int8 = wire_int8 / (ici_gbps * 1e9)
    step_f32 = max(t_mem, t_comp, t_comm_f32)
    step_int8 = max(t_mem, t_comp, t_comm_int8)
    return {
        "model": "llama-70b 80L/8192h (projection, not measured)",
        "slice": "v5e-32 as dp4 x tp8, dp over DCN",
        "assumptions": {"device_kind": V5E, "peak_tflops": peak_tflops,
                        "hbm_gbps": hbm_gbps, "ici_gbps": ici_gbps,
                        "dcn_gbps": dcn_gbps,
                        "decode_batch_per_replica": batch,
                        "weights": "bf16 (17.6 GB/chip at tp8 — over "
                                   "v5e's 16 GB HBM; int8 weights or "
                                   "tp16 needed to actually fit)"},
        "params": params,
        "t_memory_ms": round(t_mem * 1e3, 4),
        "t_compute_ms": round(t_comp * 1e3, 4),
        "t_comm_ms_fp32_collectives": round(t_comm_f32 * 1e3, 4),
        "t_comm_ms_int8_collectives": round(t_comm_int8 * 1e3, 4),
        "comm_wire_bytes_fp32": int(wire_f32),
        "comm_wire_bytes_int8": int(wire_int8),
        "comm_bytes_saved": int(wire_f32 - wire_int8),
        "dcn_step_bytes": 0,
        "dcn_note": "dp replicas are decode-independent: no per-step "
                    "collective crosses the DCN; only admission, KV "
                    "migration and weight distribution ride it",
        "bound_fp32": ("comm" if t_comm_f32 >= max(t_mem, t_comp)
                       else "memory" if t_mem >= t_comp else "compute"),
        "est_step_ms_fp32": round(step_f32 * 1e3, 4),
        "est_step_ms_int8": round(step_int8 * 1e3, 4),
    }


def sharding_report_main(artifact_path="artifacts/sharding_report_r18.json"):
    """CPU-mesh sharding-observatory report (ISSUE 8, quantized legs
    ISSUE 18): AOT-compile the tiny synthetic serving apps (paged + cb +
    the cb app with int8 quantized collectives) over a dp2 x tp2 CPU
    mesh, census every collective in the partitioned HLO (kind x
    mesh-axis comm group x wire dtype, payload bytes) and report the
    three-way compute/memory/comm-bound roofline per graph under the
    assumed chip constants (NXDI_TPU_PEAK_TFLOPS / NXDI_TPU_HBM_GBPS /
    NXDI_TPU_ICI_GBPS / NXDI_TPU_DCN_GBPS). Details carry the measured
    fp32-vs-int8 comm-roofline delta on the decode graphs and the
    analytic 70B-on-v5e-32 projection. One parseable JSON line + an
    artifact file, no TPU required: this is the hardware-free evidence
    trail for collective regressions on the serving graphs —
    `scripts/check_spmd_sharding.py` turns the same census into a red
    test against `artifacts/spmd_golden.json`."""
    from neuronx_distributed_inference_tpu.compat import force_cpu_devices
    force_cpu_devices(4)

    from neuronx_distributed_inference_tpu.telemetry import observatory

    if len(jax.devices()) < 4:
        print(json.dumps({
            "metric": "sharding_report_collective_bytes_total",
            "skipped": f"need 4 virtual CPU devices for the dp2xtp2 mesh, "
                       f"got {len(jax.devices())} (backend initialized "
                       "before the device-count flag could land)"}))
        return

    reports = _observatory_reports(mesh=True, label="sharding report",
                                   quantized=True)
    total_bytes = sum(r["totals"]["collective_bytes"]
                      for r in reports.values())
    bounds = {f"{name}/{g['kind']}/{g['bucket']}": g["roofline"]["bound"]
              for name, r in reports.items() for g in r["graphs"]}

    def decode_leg(name):
        # the cb decode step (bucket "b<batch>") — the graph the
        # quantized ring rewrites
        g = next(g for g in reports[name]["graphs"]
                 if g["kind"] == "decode")
        return {"collective_bytes": g["collective_bytes"],
                "t_comm_ms": g["roofline"]["t_comm_ms"],
                "comm_bytes_saved": g["roofline"]["comm_bytes_saved"]}

    f32_leg, int8_leg = decode_leg("cb"), decode_leg("cb_int8")
    payload = {
        "metric": "sharding_report_collective_bytes_total",
        "value": total_bytes,
        "unit": "collective_payload_bytes_all_multichip_graphs",
        "details": {
            "schema": observatory.SHARDING_REPORT_SCHEMA,
            "model": "llama-tiny 2L/64h (synthetic fp32)",
            "device": str(jax.devices()[0]),
            "mesh": reports["paged"]["mesh"],
            "roofline_bounds": bounds,
            "quantized_comm_delta": {
                "graph": "cb decode b2",
                "collective_dtype": "int8",
                "fp32": f32_leg,
                "int8": int8_leg,
                "comm_bytes_saved": int8_leg["comm_bytes_saved"],
            },
            "projection_70b_v5e32": _project_70b_v5e32(),
            "apps": reports,
        },
    }
    _emit_report_artifact(payload, artifact_path, "sharding-report")


def main():
    configure_compile_cache()
    if "--host-overhead" in sys.argv[1:]:
        return host_overhead_main()
    if "--prefill-overhead" in sys.argv[1:]:
        return prefill_overhead_main()
    if "--spec-overhead" in sys.argv[1:]:
        return spec_overhead_main()
    if "--spec-sampled" in sys.argv[1:]:
        return spec_sampled_main()
    if "--ragged-overhead" in sys.argv[1:]:
        return ragged_overhead_main()
    if "--perf-snapshot" in sys.argv[1:]:
        return perf_snapshot_main()
    if "--serving-load" in sys.argv[1:]:
        return serving_load_main()
    if "--fleet-load" in sys.argv[1:]:
        return fleet_load_main()
    if "--autoscale-report" in sys.argv[1:]:
        return autoscale_report_main()
    if "--slo-report" in sys.argv[1:]:
        return slo_report_main()
    if "--chaos-report" in sys.argv[1:]:
        return chaos_report_main()
    if "--lora-churn" in sys.argv[1:]:
        return lora_churn_main()
    if "--graph-report" in sys.argv[1:]:
        return graph_report_main()
    if "--sharding-report" in sys.argv[1:]:
        return sharding_report_main()
    if "--lint-report" in sys.argv[1:]:
        return lint_report_main()
    # the default mode measures the chip: with no TPU it fails, naming the
    # device jax found, and prints no number (utils/device.require_tpu)
    try:
        device = require_tpu()
    except NoAcceleratorError as e:
        print(f"bench.py: {e}", file=sys.stderr)
        return 1
    return _tpu_bench_main(device)


def _tpu_bench_main(device):
    from neuronx_distributed_inference_tpu.config import (InferenceConfig,
                                                          TpuConfig)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                                 build_mesh)
    from neuronx_distributed_inference_tpu import telemetry

    # an unknown chip has no roofline to be compared with: fail before
    # measuring, not after
    hbm_gbps = device_peaks(device["kind"]).hbm_gbps
    reg = telemetry.enable()

    def heartbeat(tag):
        line = reg.stats_line()
        if line:
            print(f"[bench telemetry | {tag}] {line}", file=sys.stderr)

    batch = 2
    prompt_len = 128
    seq_len = 1024
    chunk = 64

    hf_attrs = dict(  # Llama-3.2-1B geometry
        model_type="llama", hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
        head_dim=64, vocab_size=128256, rms_norm_eps=1e-5, rope_theta=500000.0,
        hidden_act="silu", tie_word_embeddings=True,
    )
    # TKG seq bucketing on: decode graphs read only cache[:bucket] — early
    # decode streams a fraction of the allocated KV (reference: TKG seq
    # buckets, autobucketing.py:226)
    tcfg = TpuConfig(batch_size=batch, seq_len=seq_len,
                     max_context_length=prompt_len, dtype="bfloat16",
                     enable_bucketing=True,
                     context_encoding_buckets=[prompt_len],
                     decode_chunk_tokens=chunk)
    icfg = LlamaInferenceConfig(tcfg, **hf_attrs)
    mesh = build_mesh(MeshConfig(tp=1))
    app = CausalLMApplication(None, icfg, LlamaFamily, mesh=mesh)
    app.init_random_weights(seed=0)
    app.init_cache()

    prompt = np.random.default_rng(0).integers(
        0, 1000, size=(batch, prompt_len), dtype=np.int32)

    # warmup / compile
    t0 = time.perf_counter()
    res = app.generate(prompt, max_new_tokens=chunk + 1)
    compile_wall = time.perf_counter() - t0
    # the heartbeat line carries the cold-start compile cost on stderr
    # (observatory gauge, kind=warmup for the whole ladder)
    from neuronx_distributed_inference_tpu.telemetry import \
        metrics as tmetrics
    tmetrics.compile_seconds_gauge(reg).set(compile_wall, kind="warmup",
                                            bucket="all")
    heartbeat("after compile+warmup")

    # Timing methodology: every timed region ends in a device->host fetch
    # of the last output (np.asarray), which — like block_until_ready —
    # returns only when the chip has finished. Dispatch is asynchronous, so
    # a single call's wall time still includes the host's enqueue and the
    # fetch; all reported timings are therefore the SLOPE between two
    # chained runs of different lengths, where that fixed cost cancels.

    # TTFT: n chained prefills (cache rows rotate through seq_ids), fetch
    # once; slope over n cancels the fetch latency
    def prefill_n(n):
        app.reset()
        t0 = time.perf_counter()
        for _ in range(n):
            out = app._run_prefill(prompt, np.full((batch,), prompt_len,
                                                   np.int32))
        np.asarray(out["tokens"])
        return time.perf_counter() - t0, out

    prefill_n(1)                      # warm
    t_a, _ = prefill_n(2)
    t_b, out = prefill_n(10)
    ttft_ms = (t_b - t_a) / 8 * 1e3
    heartbeat("after prefill phase")

    # decode throughput: fused decode loop, slope between two round counts
    first = np.asarray(out["tokens"]).astype(np.int32)
    steps = chunk

    def decode_rounds(n):
        positions = np.full((batch,), prompt_len, np.int32)
        last = first
        t0 = time.perf_counter()
        for _ in range(n):
            o = app._run_decode_loop(last, positions, steps)
            last = o["tokens"][:, -1]          # stays on device
            positions = positions + steps
        np.asarray(o["tokens"])
        return time.perf_counter() - t0

    decode_rounds(1)                  # warm
    t2 = min(decode_rounds(2) for _ in range(2))
    t8 = min(decode_rounds(8) for _ in range(2))
    per_step = (t8 - t2) / (6 * steps)
    tok_s = batch / per_step
    heartbeat("after decode phase")

    # per-step breakdown: amortized slope of the lm_head alone — the rest
    # of the step is the layer stack + sampling. A failure here fails the
    # run like any other phase.
    from neuronx_distributed_inference_tpu.models import model_base

    def make_head(n):
        def head_loop(params):
            def body(h, _):
                lg = model_base._lm_head(app.spec, params, h)
                return h + lg.max(axis=-1).astype(h.dtype)[..., None] * 1e-9, None
            h0 = jnp.ones((batch, 1, app.spec.hidden_size),
                          app.spec.dtype)
            h, _ = jax.lax.scan(body, h0, None, length=n)
            return h.sum().astype(jnp.float32)
        return jax.jit(head_loop)

    f1, f2 = make_head(16), make_head(64)
    np.asarray(f1(app.params)); np.asarray(f2(app.params))

    def t(f):
        t0 = time.perf_counter()
        np.asarray(f(app.params))
        return time.perf_counter() - t0
    h1 = min(t(f1) for _ in range(2))
    h2 = min(t(f2) for _ in range(2))
    head_ms = max((h2 - h1) / 48 * 1e3, 0.0)

    # sampling-only slope: sample_dp over a fixed logits tensor
    from neuronx_distributed_inference_tpu.ops import \
        sampling as sampling_ops

    def make_samp(n):
        def samp_loop(lg):
            def body(c, _):
                tok = sampling_ops.sample_dp(lg + c * 0.0, None, None,
                                             jax.random.PRNGKey(0))
                return c + tok.sum().astype(jnp.float32) * 1e-9, None
            c, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None,
                                length=n)
            return c
        return jax.jit(samp_loop)

    lg0 = jnp.zeros((batch, app.spec.padded_vocab), jnp.float32)
    s1, s2 = make_samp(16), make_samp(64)
    np.asarray(s1(lg0)); np.asarray(s2(lg0))

    def ts(f):
        t0 = time.perf_counter()
        np.asarray(f(lg0))
        return time.perf_counter() - t0
    samp_ms = max((min(ts(s2) for _ in range(2))
                   - min(ts(s1) for _ in range(2))) / 48 * 1e3, 0.0)
    breakdown = {
        "lm_head_ms_per_step": round(head_ms, 3),
        "sampling_ms_per_step": round(samp_ms, 3),
        "layers_plus_dispatch_ms_per_step": round(
            max(per_step * 1e3 - head_ms - samp_ms, 0.0), 3),
    }

    # roofline: decode streams params + live KV once per step
    param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(app.params))
    kv_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(app.cache))
    roofline = hbm_gbps * 1e9 / (param_bytes + kv_bytes) * batch

    print(json.dumps({
        "metric": "decode_throughput_llama1b_bf16_bs2",
        "value": round(tok_s, 2),
        "unit": "tokens/s",
        "vs_baseline": round(tok_s / roofline, 4),
        "details": {
            "ttft_ms_prompt128": round(ttft_ms, 2),
            "per_step_latency_ms": round(per_step * 1e3, 3),
            "per_step_breakdown": breakdown,
            "compile_plus_first_gen_s": round(compile_wall, 1),
            "roofline_tok_s": round(roofline, 1),
            "param_bytes": param_bytes,
            "kv_bytes": kv_bytes,
            "device": device,
            "hbm_gbps": hbm_gbps,
            "telemetry_stats": reg.stats_line(),
        },
    }))


if __name__ == "__main__":
    # propagate per-mode return codes (chaos/lint reports return 1 on a
    # red result — a regression must fail the invoking CI step); mains
    # returning None still exit 0
    sys.exit(main())
