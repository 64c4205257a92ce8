"""End-to-end accuracy: tiny random-weight Llama vs HF CPU golden
(reference test strategy: tiny 4-layer integration configs + HF-CPU
logit-matching, SURVEY §4 / utils/accuracy.py)."""

import jax
import numpy as np
import pytest
import torch

from neuronx_distributed_inference_tpu.config import InferenceConfig, TpuConfig
from neuronx_distributed_inference_tpu.models.application import CausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (LlamaFamily,
                                                            LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.parallel.mesh import MeshConfig, build_mesh

from conftest import tiny_llama_hf_config


@pytest.fixture(scope="module")
def hf_model_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(0)
    cfg = LlamaConfig(**tiny_llama_hf_config())
    model = LlamaForCausalLM(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("tiny_llama")
    model.save_pretrained(d, safe_serialization=True)
    return str(d)


def _build_app(hf_model_dir, tp=1, **cfg_over):
    base = dict(batch_size=2, seq_len=64, dtype="float32",
                output_logits=True,
                enable_bucketing=False, tp_degree=tp)
    base.update(cfg_over)
    tcfg = TpuConfig(**base)
    from neuronx_distributed_inference_tpu.config import load_pretrained_config
    icfg = LlamaInferenceConfig(tcfg, load_config=load_pretrained_config(hf_model_dir))
    mesh = build_mesh(MeshConfig(tp=tp))
    app = CausalLMApplication(hf_model_dir, icfg, LlamaFamily, mesh=mesh)
    app.load_weights()
    app.init_cache()
    return app


def _hf_golden(hf_model_dir, input_ids):
    from transformers import LlamaForCausalLM
    model = LlamaForCausalLM.from_pretrained(hf_model_dir)
    model.eval()
    with torch.no_grad():
        out = model(torch.tensor(input_ids))
    return out.logits.numpy()


def test_prefill_logits_match_hf(hf_model_dir):
    app = _build_app(hf_model_dir)
    rng = np.random.default_rng(0)
    input_ids = rng.integers(0, 512, size=(2, 12), dtype=np.int64)
    out = app._run_prefill(input_ids.astype(np.int32),
                           np.full((2,), 12, np.int32))
    golden = _hf_golden(hf_model_dir, input_ids)
    ours = np.asarray(out["logits"])
    np.testing.assert_allclose(ours, golden, atol=2e-3, rtol=1e-3)


def test_greedy_generation_matches_hf(hf_model_dir):
    app = _build_app(hf_model_dir)
    rng = np.random.default_rng(1)
    input_ids = rng.integers(0, 512, size=(2, 8), dtype=np.int64)

    from transformers import LlamaForCausalLM
    model = LlamaForCausalLM.from_pretrained(hf_model_dir)
    model.eval()
    with torch.no_grad():
        hf_seq = model.generate(torch.tensor(input_ids), max_new_tokens=16,
                                do_sample=False).numpy()

    res = app.generate(input_ids.astype(np.int32), max_new_tokens=16)
    np.testing.assert_array_equal(res["sequences"], hf_seq)


def test_ragged_batch_right_padding(hf_model_dir):
    """Rows of different lengths, right-padded (reference:
    hf_adapter right-padding-aware prepare_inputs :259-335)."""
    app = _build_app(hf_model_dir)
    rng = np.random.default_rng(2)
    ids_a = rng.integers(1, 512, size=(1, 10), dtype=np.int64)
    ids_b = rng.integers(1, 512, size=(1, 6), dtype=np.int64)

    from transformers import LlamaForCausalLM
    model = LlamaForCausalLM.from_pretrained(hf_model_dir)
    model.eval()
    with torch.no_grad():
        seq_a = model.generate(torch.tensor(ids_a), max_new_tokens=8,
                               do_sample=False).numpy()
        seq_b = model.generate(torch.tensor(ids_b), max_new_tokens=8,
                               do_sample=False).numpy()

    batch = np.zeros((2, 10), np.int32)
    mask = np.zeros((2, 10), np.int32)
    batch[0, :10] = ids_a[0]
    mask[0, :10] = 1
    batch[1, :6] = ids_b[0]
    mask[1, :6] = 1
    res = app.generate(batch, attention_mask=mask, max_new_tokens=8)
    np.testing.assert_array_equal(res["sequences"][0], seq_a[0])
    np.testing.assert_array_equal(res["generated"][1], seq_b[0, 6:])


def test_decode_loop_matches_single_steps(hf_model_dir):
    """Fused multi-token decode (lax.scan) == step-by-step decode."""
    app = _build_app(hf_model_dir, output_logits=False, decode_chunk_tokens=4)
    rng = np.random.default_rng(3)
    input_ids = rng.integers(0, 512, size=(2, 8), dtype=np.int64)
    res_fused = app.generate(input_ids.astype(np.int32), max_new_tokens=12)

    app2 = _build_app(hf_model_dir, output_logits=False, decode_chunk_tokens=1)
    res_step = app2.generate(input_ids.astype(np.int32), max_new_tokens=12)
    np.testing.assert_array_equal(res_fused["sequences"], res_step["sequences"])


def test_tp8_sharded_matches_tp1(hf_model_dir):
    """TP=8 on the virtual CPU mesh must match TP=1 (collectives correctness)."""
    app1 = _build_app(hf_model_dir, tp=1)
    app8 = _build_app(hf_model_dir, tp=8)
    rng = np.random.default_rng(4)
    input_ids = rng.integers(0, 512, size=(2, 8), dtype=np.int64)
    r1 = app1.generate(input_ids.astype(np.int32), max_new_tokens=10)
    r8 = app8.generate(input_ids.astype(np.int32), max_new_tokens=10)
    np.testing.assert_array_equal(r1["sequences"], r8["sequences"])

    out1 = np.asarray(app1.reset()._run_prefill(
        input_ids.astype(np.int32), np.full((2,), 8, np.int32))["logits"])
    out8 = np.asarray(app8.reset()._run_prefill(
        input_ids.astype(np.int32), np.full((2,), 8, np.int32))["logits"])
    np.testing.assert_allclose(out1, out8, atol=2e-3, rtol=1e-3)
