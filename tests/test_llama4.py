"""Llama4 text golden tests vs HF CPU (reference: models/llama4/
modeling_llama4_text.py; tiny-random-weight golden strategy, SURVEY §4).

Tiny config exercises every llama4 delta at once: chunked attention (chunk=4
within a 12-token prompt), a NoPE global layer (interval 4), attention
temperature tuning (floor_scale=4 so scales vary in-range), weightless qk L2
norm, and interleaved dense/MoE (step=2, input-scaled sigmoid routing +
shared expert)."""

import numpy as np
import pytest
import torch

from neuronx_distributed_inference_tpu.config import (TpuConfig,
                                                      load_pretrained_config)
from neuronx_distributed_inference_tpu.models.application import \
    CausalLMApplication
from neuronx_distributed_inference_tpu.models.llama4 import (
    Llama4Family, Llama4InferenceConfig)
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh)
from neuronx_distributed_inference_tpu.utils.testing import \
    check_generation_golden


def _tiny_cfg(**over):
    cfg = dict(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=32,        # expert / shared intermediate
        intermediate_size_mlp=64,    # dense-layer intermediate
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        num_experts_per_tok=1,
        num_local_experts=4,
        interleave_moe_layer_step=2,
        no_rope_layer_interval=4,
        attention_chunk_size=4,
        attn_temperature_tuning=True,
        floor_scale=4.0,
        attn_scale=0.1,
        use_qk_norm=True,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        max_position_embeddings=256,
        tie_word_embeddings=False,
        torch_dtype="float32",
    )
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def hf_dir(tmp_path_factory):
    from transformers import Llama4ForCausalLM, Llama4TextConfig
    torch.manual_seed(0)
    model = Llama4ForCausalLM(Llama4TextConfig(**_tiny_cfg()))
    model.eval()
    d = tmp_path_factory.mktemp("tiny_llama4")
    model.save_pretrained(d, safe_serialization=True)
    return str(d)


def _build_app(hf_dir, **tcfg_over):
    base = dict(batch_size=2, seq_len=64, dtype="float32",
                output_logits=True,
                enable_bucketing=False)
    base.update(tcfg_over)
    tcfg = TpuConfig(**base)
    icfg = Llama4InferenceConfig(tcfg,
                                 load_config=load_pretrained_config(hf_dir))
    app = CausalLMApplication(hf_dir, icfg, Llama4Family,
                              mesh=build_mesh(MeshConfig(tp=1)))
    app.load_weights()
    app.init_cache()
    return app


def test_llama4_spec_structure(hf_dir):
    app = _build_app(hf_dir)
    spec = app.spec
    # layer 3 is NoPE global; the rest rope+chunked
    assert spec.layer_pattern == (True, True, True, False)
    assert spec.attn_chunk == 4 and spec.nope_global and spec.qk_l2_norm
    assert spec.attn_temp == (4.0, 0.1)
    # interleave step 2 -> layers 1, 3 MoE
    assert spec.moe_pattern == (False, True, False, True)
    assert spec.moe.input_scaled and spec.moe.router_act == "sigmoid"
    assert spec.moe.shared_intermediate == 32
    assert spec.intermediate_size == 64  # dense layers use the _mlp width
    assert "layers" in app.params and "moe_layers" in app.params


def test_llama4_golden_generation(hf_dir):
    from transformers import Llama4ForCausalLM
    hf = Llama4ForCausalLM.from_pretrained(hf_dir)
    hf.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(1, 500, size=(2, 12)).astype(np.int64)
    app = _build_app(hf_dir)
    check_generation_golden(app, ids, hf, max_new_tokens=8, atol=8e-3)


def test_llama4_vision_golden(tmp_path):
    """Pixel-values -> tokens through the vision tower + projector + text
    stack vs HF Llama4ForConditionalGeneration (reference:
    modeling_llama4_vision.py golden parity)."""
    from transformers import Llama4Config, Llama4ForConditionalGeneration
    from neuronx_distributed_inference_tpu.models.image_to_text import \
        ImageToTextInferenceConfig
    from neuronx_distributed_inference_tpu.models.llama4 import \
        Llama4VLApplication
    torch.manual_seed(3)
    vision_cfg = dict(
        image_size=16, patch_size=8, num_channels=3,
        hidden_size=32, intermediate_size=128,     # = hidden / ratio^2
        num_hidden_layers=2, num_attention_heads=4,
        pixel_shuffle_ratio=0.5,
        projector_input_dim=48, projector_output_dim=48,
        vision_output_dim=48, rope_theta=10000.0,
        intermediate_layers_indices=[1],
    )
    cfg = Llama4Config(
        text_config=_tiny_cfg(vocab_size=512),
        vision_config=vision_cfg,
        image_token_index=511, boi_token_index=509, eoi_token_index=510)
    model = Llama4ForConditionalGeneration(cfg)
    model.eval()
    d = str(tmp_path / "vl")
    model.save_pretrained(d, safe_serialization=True)

    rng = np.random.default_rng(5)
    pixels = rng.standard_normal((1, 3, 16, 16)).astype(np.float32)
    # 1 image => (16/8)^2 * 0.5^2 = 1 feature token
    ids = np.concatenate([
        rng.integers(1, 500, size=(1, 4)),
        np.full((1, 1), 511), rng.integers(1, 500, size=(1, 4))],
        axis=1).astype(np.int64)
    with torch.no_grad():
        hf_seq = model.generate(torch.tensor(ids),
                                pixel_values=torch.tensor(pixels),
                                max_new_tokens=6, do_sample=False).numpy()

    tcfg = TpuConfig(batch_size=1, seq_len=64, dtype="float32",
                     output_logits=True,
                     enable_bucketing=False)
    icfg = ImageToTextInferenceConfig(tcfg, load_config=load_pretrained_config(d))
    app = Llama4VLApplication(d, icfg).load_weights()
    out = app.generate(ids, pixels, max_new_tokens=6)
    np.testing.assert_array_equal(out["generated"][:, :6], hf_seq[:, 9:])


def test_llama4_all_moe_variant(tmp_path):
    """interleave step 1 (Scout-like): every layer MoE, no dense stack."""
    from transformers import Llama4ForCausalLM, Llama4TextConfig
    torch.manual_seed(1)
    model = Llama4ForCausalLM(Llama4TextConfig(
        **_tiny_cfg(interleave_moe_layer_step=1, num_hidden_layers=2,
                    no_rope_layer_interval=2)))
    model.eval()
    d = str(tmp_path / "m")
    model.save_pretrained(d, safe_serialization=True)
    app = _build_app(d)
    assert app.spec.moe_pattern == (True, True)
    assert "layers" not in app.params
    rng = np.random.default_rng(2)
    ids = rng.integers(1, 500, size=(2, 9)).astype(np.int64)
    check_generation_golden(app, ids, model, max_new_tokens=6, atol=8e-3)
