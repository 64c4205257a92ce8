"""The serving adapter (reference: the vLLM-facing contract of
models/model_wrapper.py:1297-1440): continuous-batching begin/step/release
keyed by seq_ids over the paged app, plus the paged app's batch-mismatch
repad shim."""

import numpy as np

from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


def _ref_tokens(prompt, n):
    """Plain single-request generate as the golden."""
    tcfg = TpuConfig(batch_size=1, seq_len=64, dtype="float32",
                     enable_bucketing=False)
    app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                              LlamaFamily)
    app.init_random_weights(7).init_cache()
    out = app.generate(np.asarray([prompt]), max_new_tokens=n)
    return np.asarray(out["generated"])[0]


def test_paged_engine_adapter_interleaved():
    """Two requests joining at different times must each reproduce their
    single-request greedy tokens."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    eng = PagedEngineAdapter(app)

    rng = np.random.default_rng(0)
    p1 = rng.integers(1, 500, size=9).tolist()
    p2 = rng.integers(1, 500, size=12).tolist()
    want1 = _ref_tokens(p1, 8)
    want2 = _ref_tokens(p2, 8)

    got1 = [eng.add_requests([0], [p1])[0]]
    for _ in range(3):
        got1.append(eng.step()[0])
    got2 = [eng.add_requests([1], [p2])[1]]
    for _ in range(4):
        res = eng.step()
        got1.append(res[0])
        got2.append(res[1])
    for _ in range(3):
        got2.append(eng.step([1])[1])
    np.testing.assert_array_equal(got1, want1)
    np.testing.assert_array_equal(got2, want2)
    eng.release([0, 1])
    assert 0 not in app.kv_mgr.tables and 1 not in app.kv_mgr.tables
    assert eng.free_capacity == 4


def test_paged_generate_repad_shim():
    """b != compiled batch on the PAGED app routes through the repad shim
    instead of silently compiling fresh graphs."""
    def build(batch):
        tcfg = TpuConfig(batch_size=batch, seq_len=64, dtype="float32",
                         enable_bucketing=False, is_block_kv_layout=True,
                         pa_block_size=8)
        app = PagedCausalLMApplication(
            None, LlamaInferenceConfig(tcfg, **HF), LlamaFamily)
        app.init_random_weights(7).init_cache()
        return app

    rng = np.random.default_rng(1)
    ids = rng.integers(1, 500, size=(3, 10), dtype=np.int64)
    app4 = build(4)
    got = app4.generate(ids, max_new_tokens=8)       # 3 rows on a batch-4 app
    app1 = build(3)
    want = app1.generate(ids, max_new_tokens=8)
    np.testing.assert_array_equal(got["generated"], want["generated"])
    big = rng.integers(1, 500, size=(5, 10), dtype=np.int64)
    app4.release()
    got_big = app4.generate(big, max_new_tokens=8)   # 5 rows -> sub-batched
    assert got_big["generated"].shape[0] == 5
