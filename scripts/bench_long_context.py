#!/usr/bin/env python
"""Long-context prefill benchmark on the real chip:
8k-token windowed context encoding on the bench model geometry — prefill
tokens/s and wall time, printed as one JSON line."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    CausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (LlamaFamily,
                                                            LlamaInferenceConfig)

S = int(os.environ.get("NXDI_LONG_S", "8192"))
W = int(os.environ.get("NXDI_LONG_W", "2048"))
hf_attrs = dict(
    model_type="llama", hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
    head_dim=64, vocab_size=128256, rms_norm_eps=1e-5, rope_theta=500000.0,
    hidden_act="silu", tie_word_embeddings=True,
)
tcfg = TpuConfig(batch_size=1, seq_len=S + 64, max_context_length=S,
                 dtype="bfloat16", enable_bucketing=False,
                 windowed_context_encoding=W)
app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf_attrs),
                          LlamaFamily)
app.init_random_weights(0).init_cache()
prompt = np.random.default_rng(0).integers(0, 1000, size=(1, S),
                                           dtype=np.int32)

t0 = time.perf_counter()
out = app.generate(prompt, max_new_tokens=2)
compile_s = time.perf_counter() - t0

times = []
for _ in range(3):
    app.reset()
    t0 = time.perf_counter()
    out = app.generate(prompt, max_new_tokens=2)
    times.append(time.perf_counter() - t0)
best = min(times)
print(json.dumps({
    "metric": f"long_context_prefill_{S}_tok_s",
    "value": round(S / best, 1),
    "unit": "tokens/s",
    "vs_baseline": None,
    "details": {"seq": S, "window": W, "wall_s": round(best, 2),
                "compile_plus_first_s": round(compile_s, 1),
                "includes": "windowed CTE prefill + 2 decode steps"},
}))
