"""inference_demo CLI (reference: inference_demo.py — argparse mirror of the
config system :99-408, run flow :493-680).

Subcommand ``run`` compiles + loads a model, generates from prompts, and
optionally runs the accuracy gates and benchmark, mirroring the reference's
``inference_demo --model-type llama --task-type causal-lm run ...``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import List, Optional

import numpy as np

logger = logging.getLogger("nxdi_tpu")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="inference_demo_tpu")
    p.add_argument("--model-type", default=None,
                   help="model family (llama/mistral/qwen2/qwen3/...); "
                        "default: read from config.json")
    p.add_argument("--task-type", default="causal-lm")
    sub = p.add_subparsers(dest="command", required=True)
    run = sub.add_parser("run", help="compile, load, generate")
    run.add_argument("--model-path", required=True)
    run.add_argument("--compiled-model-path", default=None)
    run.add_argument("--prompt", action="append", default=None)
    run.add_argument("--prompt-len", type=int, default=16,
                     help="random-token prompt length when no --prompt given "
                          "or no tokenizer available")
    run.add_argument("--tp-degree", type=int, default=1)
    run.add_argument("--cp-degree", type=int, default=1)
    run.add_argument("--ep-degree", type=int, default=1)
    run.add_argument("--attention-dp-degree", type=int, default=1)
    run.add_argument("--sequence-parallel", action="store_true")
    run.add_argument("--flash-decoding", action="store_true")
    run.add_argument("--batch-size", type=int, default=1)
    run.add_argument("--max-context-length", type=int, default=128)
    run.add_argument("--seq-len", type=int, default=256)
    run.add_argument("--dtype", default="bfloat16",
                     choices=["bfloat16", "float32", "float16"])
    run.add_argument("--max-new-tokens", type=int, default=64)
    run.add_argument("--random-weights", action="store_true",
                     help="skip checkpoint load; synthetic weights")
    run.add_argument("--on-cpu", action="store_true",
                     help="run on virtual CPU devices (reference --on-cpu)")
    run.add_argument("--enable-bucketing", action="store_true", default=True)
    run.add_argument("--no-bucketing", dest="enable_bucketing",
                     action="store_false")
    run.add_argument("--decode-chunk-tokens", type=int, default=1)
    run.add_argument("--enable-2d-bucketing", action="store_true",
                     help="batch x seq TKG buckets + paged table-width "
                          "buckets (reference: autobucketing.py:22-64,203)")
    run.add_argument("--windowed-context-encoding", type=int, default=None,
                     help="prefill window size for >=32k prompts "
                          "(reference: model_base.py:878-933)")
    # quantization (reference: models/config.py:216-241)
    run.add_argument("--quantized", action="store_true")
    run.add_argument("--quantization-dtype", default="int8",
                     choices=["int8", "fp8", "mxfp4"])
    run.add_argument("--quantization-type", default="per_channel_symmetric",
                     choices=["per_channel_symmetric", "per_tensor_symmetric",
                              "blockwise_symmetric"])
    run.add_argument("--moe-tkg-ep-degree", type=int, default=None,
                     help="hybrid CTE/TKG expert sharding: 1 = decode "
                          "all-experts-local (reference "
                          "HybridShardingConfig)")
    run.add_argument("--kv-cache-dtype", default=None)
    run.add_argument("--kv-cache-quant", action="store_true")
    # paged KV / prefix caching / chunked prefill
    run.add_argument("--block-kv", action="store_true",
                     help="paged (block) KV cache layout")
    run.add_argument("--prefix-caching", action="store_true")
    run.add_argument("--chunked-prefill", action="store_true")
    run.add_argument("--pa-block-size", type=int, default=32)
    # speculation (reference: --speculation-length / --draft-model-path)
    run.add_argument("--speculation-length", type=int, default=0)
    run.add_argument("--draft-model-path", default=None)
    # LoRA serving
    run.add_argument("--lora-ckpt", action="append", default=None,
                     metavar="NAME=PATH", help="PEFT adapter dir, repeatable")
    run.add_argument("--max-loras", type=int, default=4)
    run.add_argument("--max-lora-rank", type=int, default=16)
    run.add_argument("--adapter-id", type=int, default=None,
                     help="adapter slot used for this run's requests")
    # sampling
    run.add_argument("--on-device-sampling", action="store_true")
    run.add_argument("--do-sample", action="store_true")
    run.add_argument("--top-k", type=int, default=1)
    run.add_argument("--top-p", type=float, default=1.0)
    run.add_argument("--temperature", type=float, default=1.0)
    # accuracy (reference: --check-accuracy-mode)
    run.add_argument("--check-accuracy-mode", default="skip-accuracy-check",
                     choices=["skip-accuracy-check", "token-matching",
                              "logit-matching"])
    run.add_argument("--divergence-difference-tol", type=float, default=0.001)
    run.add_argument("--num-tokens-to-check", type=int, default=16)
    # benchmark (reference: --benchmark)
    run.add_argument("--benchmark", action="store_true")
    run.add_argument("--benchmark-runs", type=int, default=5)
    run.add_argument("--benchmark-report-path",
                     default="benchmark_report.json")
    # observability: enable the runtime telemetry registry and dump its JSON
    # snapshot (metrics + request spans) to PATH on exit
    run.add_argument("--metrics-json", default=None, metavar="PATH",
                     help="enable runtime telemetry; write the registry "
                          "snapshot (metrics + spans) as JSON to PATH")
    run.add_argument("--seed", type=int, default=0)
    return p


def _force_cpu(n: int = 8):
    from .compat import force_cpu_devices
    force_cpu_devices(n)


def run_inference(args) -> int:
    if args.on_cpu:
        _force_cpu(max(args.tp_degree, 8))
    metrics_reg = None
    if args.metrics_json:
        from . import telemetry
        metrics_reg = telemetry.enable()
    try:
        return _run_inference(args)
    finally:
        if metrics_reg is not None:
            # never let a bad --metrics-json path shadow the run's own error
            try:
                with open(args.metrics_json, "w") as f:
                    json.dump(metrics_reg.snapshot(), f, indent=2)
            except OSError as e:
                logger.error("could not write telemetry snapshot to %s: %s",
                             args.metrics_json, e)
            else:
                line = metrics_reg.stats_line()
                if line:
                    logger.info("telemetry: %s", line)
                logger.info("telemetry snapshot written to %s",
                            args.metrics_json)


def _run_inference(args) -> int:
    from .config import (InferenceConfig, LoraServingConfig, MoEConfig,
                         OnDeviceSamplingConfig, SpeculationConfig, TpuConfig,
                         load_pretrained_config)
    from .models.application import (CausalLMApplication,
                                     PagedCausalLMApplication)
    from .models.family import get_family

    sampling_cfg = None
    if args.on_device_sampling or args.do_sample:
        sampling_cfg = OnDeviceSamplingConfig(
            do_sample=args.do_sample, top_k=args.top_k, top_p=args.top_p,
            temperature=args.temperature)
    lora_cfg = None
    lora_paths = {}
    if args.lora_ckpt:
        for item in args.lora_ckpt:
            name, _, path = item.partition("=")
            lora_paths[name] = path
        lora_cfg = LoraServingConfig(max_loras=args.max_loras,
                                     max_lora_rank=args.max_lora_rank,
                                     lora_ckpt_paths=lora_paths)
    spec_cfg = None
    if args.speculation_length > 0:
        spec_cfg = SpeculationConfig(
            speculation_length=args.speculation_length,
            draft_model_path=args.draft_model_path)

    def make_tcfg(**over):
        kw = dict(
            batch_size=args.batch_size, seq_len=args.seq_len,
            max_context_length=args.max_context_length, dtype=args.dtype,
            tp_degree=args.tp_degree, cp_degree=args.cp_degree,
            ep_degree=args.ep_degree,
            attention_dp_degree=args.attention_dp_degree,
            sequence_parallel_enabled=args.sequence_parallel,
            flash_decoding_enabled=args.flash_decoding,
            enable_bucketing=args.enable_bucketing,
            enable_2d_bucketing=args.enable_2d_bucketing,
            windowed_context_encoding=args.windowed_context_encoding,
            decode_chunk_tokens=args.decode_chunk_tokens,
            on_device_sampling_config=sampling_cfg,
            quantized=args.quantized,
            quantization_dtype=args.quantization_dtype,
            quantization_type=args.quantization_type,
            kv_cache_dtype=args.kv_cache_dtype,
            kv_cache_quant=args.kv_cache_quant,
            is_block_kv_layout=args.block_kv or args.prefix_caching
            or args.chunked_prefill,
            is_prefix_caching=args.prefix_caching,
            is_chunked_prefill=args.chunked_prefill,
            pa_block_size=args.pa_block_size,
            lora_config=lora_cfg,
            moe_config=(MoEConfig(moe_tkg_ep_degree=args.moe_tkg_ep_degree)
                        if args.moe_tkg_ep_degree is not None else None),
            output_logits=args.check_accuracy_mode == "logit-matching",
            seed=args.seed)
        kw.update(over)
        return TpuConfig(**kw)

    tcfg = make_tcfg(speculation_config=spec_cfg)

    # model family from config.json unless overridden
    with open(os.path.join(args.model_path, "config.json")) as f:
        model_type = args.model_type or json.load(f).get("model_type")
    family = get_family(model_type)
    icfg = family.config_cls(tcfg,
                             load_config=load_pretrained_config(args.model_path))
    app_cls = (PagedCausalLMApplication if tcfg.is_block_kv_layout
               else CausalLMApplication)
    app = app_cls(args.model_path, icfg, family)
    if args.random_weights:
        app.init_random_weights(args.seed)
    else:
        app.load_weights()
    app.init_cache()
    if lora_cfg is not None and lora_paths and not args.random_weights:
        app.load_lora_adapters(lora_paths)
    if args.compiled_model_path:
        app.compile(args.compiled_model_path)

    decoder = None
    if spec_cfg is not None and args.draft_model_path:
        from .models.speculation import SpeculativeDecoder
        with open(os.path.join(args.draft_model_path, "config.json")) as f:
            draft_type = json.load(f).get("model_type")
        d_family = get_family(draft_type)
        d_icfg = d_family.config_cls(
            make_tcfg(speculation_config=spec_cfg),
            load_config=load_pretrained_config(args.draft_model_path))
        draft = CausalLMApplication(args.draft_model_path, d_icfg, d_family)
        if args.random_weights:
            draft.init_random_weights(args.seed + 1)
        else:
            draft.load_weights()
        draft.init_cache()
        decoder = SpeculativeDecoder(app, draft)

    # build input ids: tokenizer if available, else random tokens
    tokenizer = None
    eos = None
    try:
        from transformers import AutoTokenizer
        tokenizer = AutoTokenizer.from_pretrained(args.model_path)
        eos = tokenizer.eos_token_id
    except Exception:
        logger.info("no tokenizer found; using random token prompts")
    if args.prompt and tokenizer is not None:
        prompts = args.prompt * args.batch_size
        enc = tokenizer(prompts[:args.batch_size], return_tensors="np",
                        padding=True, padding_side="right")
        input_ids = enc["input_ids"].astype(np.int32)
        attention_mask = enc["attention_mask"].astype(np.int32)
    else:
        rng = np.random.default_rng(args.seed)
        input_ids = rng.integers(
            1, icfg.vocab_size, size=(args.batch_size, args.prompt_len),
            dtype=np.int32)
        attention_mask = np.ones_like(input_ids)

    gen_kwargs = {}
    if args.adapter_id is not None:
        gen_kwargs["adapter_ids"] = np.full((args.batch_size,),
                                            args.adapter_id, np.int32)
    if decoder is not None:
        res = decoder.generate(input_ids, max_new_tokens=args.max_new_tokens,
                               eos_token_id=eos,
                               attention_mask=attention_mask)
        print(f"speculation: {res['mean_tokens_per_step']:.2f} tokens/step")
    else:
        res = app.generate(input_ids, attention_mask=attention_mask,
                           max_new_tokens=args.max_new_tokens,
                           eos_token_id=eos, **gen_kwargs)
        print(f"TTFT: {res['ttft_s'] * 1e3:.1f} ms")
    for i, row in enumerate(res["sequences"]):
        if tokenizer is not None:
            print(f"--- output {i} ---")
            print(tokenizer.decode(row, skip_special_tokens=True))
        else:
            print(f"--- output {i} --- {row.tolist()}")

    rc = 0
    if args.check_accuracy_mode != "skip-accuracy-check":
        from .utils import accuracy
        hf_model = family.load_hf_model(args.model_path)
        app.reset()
        if args.check_accuracy_mode == "token-matching":
            rep = accuracy.check_accuracy(
                app, hf_model, input_ids, attention_mask=attention_mask,
                max_new_tokens=args.num_tokens_to_check, eos_token_id=eos)
        else:
            rep = accuracy.check_accuracy_logits(
                app, hf_model, input_ids, attention_mask=attention_mask,
                max_new_tokens=args.num_tokens_to_check,
                divergence_difference_tol=args.divergence_difference_tol)
        print(rep)
        rc = 0 if rep.passed else 1

    if args.benchmark:
        from .utils.benchmark import benchmark_sampling
        app.reset()
        report = benchmark_sampling(app, input_ids,
                                    max_new_tokens=args.max_new_tokens,
                                    n_runs=args.benchmark_runs,
                                    report_path=args.benchmark_report_path)
        print(json.dumps(report, indent=2))
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(level=logging.INFO)
    args = build_parser().parse_args(argv)
    if args.command == "run":
        return run_inference(args)
    return 2


if __name__ == "__main__":
    sys.exit(main())
