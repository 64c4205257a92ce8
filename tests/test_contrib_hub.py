"""Golden tests for the contrib hub breadth wave (reference:
contrib/models/, 64 community families — SURVEY §2.7). Each family: tiny
random-weight HF model vs our converted app, teacher-forced logits +
decisive-margin token equality (utils/testing.check_generation_golden)."""

import numpy as np
import pytest
import torch

from neuronx_distributed_inference_tpu.config import (TpuConfig,
                                                      load_pretrained_config)
from neuronx_distributed_inference_tpu.models.application import \
    CausalLMApplication
from neuronx_distributed_inference_tpu.models.family import get_family
from neuronx_distributed_inference_tpu.utils.testing import \
    check_generation_golden


def _check(tmp_path, model_type, hf_model, atol=6e-3, vocab_hi=250):
    d = tmp_path / model_type
    hf_model.eval()
    hf_model.save_pretrained(d, safe_serialization=True)
    # tiny random models emit EOS-range ids freely; HF generate() would
    # right-pad finished rows while ours keeps decoding — compare unpadded
    hf_model.generation_config.eos_token_id = None
    family = get_family(model_type)
    tcfg = TpuConfig(batch_size=2, seq_len=48, dtype="float32",
                     output_logits=True, enable_bucketing=False)
    icfg = family.config_cls(tcfg, load_config=load_pretrained_config(str(d)))
    app = CausalLMApplication(str(d), icfg, family)
    app.load_weights().init_cache()
    rng = np.random.default_rng(0)
    ids = rng.integers(1, vocab_hi, size=(2, 12), dtype=np.int64)
    check_generation_golden(app, ids, hf_model, max_new_tokens=8, atol=atol)
    return app


def sharpen_attention(hf_model, by=20.0):
    """Scale the q / k projections so that the attention scores of a tiny
    random model are no longer near-uniform: at the initialiser's 0.02 a
    wrong rotary pairing (half-split for interleaved) moves no logit by the
    tolerance, and the cohere families passed with it (ISSUE 56)."""
    with torch.no_grad():
        for layer in hf_model.model.layers:
            layer.self_attn.q_proj.weight *= by
            layer.self_attn.k_proj.weight *= by
    return hf_model


def test_gpt2_matches_hf(tmp_path):
    from transformers import GPT2Config, GPT2LMHeadModel
    torch.manual_seed(0)
    cfg = GPT2Config(n_embd=64, n_head=4, n_layer=3, n_positions=128,
                     vocab_size=256, resid_pdrop=0.0, embd_pdrop=0.0,
                     attn_pdrop=0.0, torch_dtype="float32")
    app = _check(tmp_path, "gpt2", GPT2LMHeadModel(cfg))
    assert app.spec.no_rope and app.spec.learned_pos == 128
    assert not app.spec.mlp_glu and app.spec.norm_bias


def test_gpt_neox_matches_hf(tmp_path):
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM
    torch.manual_seed(0)
    cfg = GPTNeoXConfig(hidden_size=64, num_attention_heads=4,
                        num_hidden_layers=3, intermediate_size=128,
                        vocab_size=256, rotary_pct=0.25,
                        max_position_embeddings=128,
                        use_parallel_residual=True,
                        hidden_dropout=0.0, attention_dropout=0.0,
                        torch_dtype="float32")
    app = _check(tmp_path, "gpt_neox", GPTNeoXForCausalLM(cfg))
    assert app.spec.block_style == "parallel_dual"
    assert app.spec.rope.rotary_dim == 4


def test_falcon_matches_hf(tmp_path):
    from transformers import FalconConfig, FalconForCausalLM
    torch.manual_seed(0)
    cfg = FalconConfig(hidden_size=64, num_attention_heads=4,
                       num_hidden_layers=3, vocab_size=256,
                       multi_query=True, parallel_attn=True,
                       new_decoder_architecture=False, bias=False,
                       alibi=False, hidden_dropout=0.0,
                       attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "falcon", FalconForCausalLM(cfg))
    assert app.spec.block_style == "parallel_shared"
    assert app.spec.num_kv_heads == 1


def test_falcon_new_arch_matches_hf(tmp_path):
    """falcon-40b style: new_decoder_architecture (grouped fused QKV,
    separate ln_attn/ln_mlp over the block input) with biases."""
    from transformers import FalconConfig, FalconForCausalLM
    torch.manual_seed(1)
    cfg = FalconConfig(hidden_size=64, num_attention_heads=4,
                       num_kv_heads=2, num_hidden_layers=3, vocab_size=256,
                       new_decoder_architecture=True, bias=True,
                       alibi=False, hidden_dropout=0.0,
                       attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "falcon", FalconForCausalLM(cfg))
    assert app.spec.block_style == "parallel_dual"
    assert app.spec.num_kv_heads == 2 and app.spec.qkv_bias


def test_starcoder2_matches_hf(tmp_path):
    from transformers import Starcoder2Config, Starcoder2ForCausalLM
    torch.manual_seed(0)
    cfg = Starcoder2Config(hidden_size=64, num_attention_heads=4,
                           num_key_value_heads=2, num_hidden_layers=3,
                           intermediate_size=128, vocab_size=256,
                           max_position_embeddings=128, use_bias=True,
                           residual_dropout=0.0, embedding_dropout=0.0,
                           attention_dropout=0.0, sliding_window=None,
                           torch_dtype="float32")
    _check(tmp_path, "starcoder2", Starcoder2ForCausalLM(cfg))


def test_phi_matches_hf(tmp_path):
    from transformers import PhiConfig, PhiForCausalLM
    torch.manual_seed(0)
    cfg = PhiConfig(hidden_size=64, num_attention_heads=4,
                    num_hidden_layers=3, intermediate_size=128,
                    vocab_size=256, partial_rotary_factor=0.5,
                    max_position_embeddings=128, resid_pdrop=0.0,
                    embd_pdrop=0.0, attention_dropout=0.0,
                    torch_dtype="float32")
    app = _check(tmp_path, "phi", PhiForCausalLM(cfg))
    assert app.spec.block_style == "parallel_shared"
    assert app.spec.lm_head_bias


def test_gemma_v1_matches_hf(tmp_path):
    from transformers import GemmaConfig, GemmaForCausalLM
    torch.manual_seed(0)
    cfg = GemmaConfig(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      num_hidden_layers=3, intermediate_size=128,
                      vocab_size=256, max_position_embeddings=128,
                      attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "gemma", GemmaForCausalLM(cfg))
    assert app.spec.norm_offset == 1.0 and app.spec.embed_scale == 8.0


def test_olmo_matches_hf(tmp_path):
    from transformers import OlmoConfig, OlmoForCausalLM
    torch.manual_seed(0)
    cfg = OlmoConfig(hidden_size=64, num_attention_heads=4,
                     num_key_value_heads=2, num_hidden_layers=3,
                     intermediate_size=128, vocab_size=256,
                     max_position_embeddings=128, clip_qkv=8.0,
                     attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "olmo", OlmoForCausalLM(cfg))
    assert app.spec.norm_type == "layernorm" and app.spec.qkv_clip == 8.0


def test_glm4_matches_hf(tmp_path):
    from transformers import Glm4Config, Glm4ForCausalLM
    torch.manual_seed(0)
    cfg = Glm4Config(hidden_size=64, num_attention_heads=4,
                     num_key_value_heads=2, num_hidden_layers=3,
                     intermediate_size=96, vocab_size=256,
                     partial_rotary_factor=0.5, head_dim=16,
                     max_position_embeddings=128, attention_bias=True,
                     pad_token_id=0, eos_token_id=1,
                     attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "glm4", Glm4ForCausalLM(cfg))
    assert app.spec.sandwich_norm and app.spec.rope_interleaved


def test_stablelm_matches_hf(tmp_path):
    from transformers import StableLmConfig, StableLmForCausalLM
    torch.manual_seed(0)
    cfg = StableLmConfig(hidden_size=64, num_attention_heads=4,
                         num_key_value_heads=2, num_hidden_layers=3,
                         intermediate_size=128, vocab_size=256,
                         partial_rotary_factor=0.25,
                         max_position_embeddings=128, use_qkv_bias=False,
                         attention_dropout=0.0, torch_dtype="float32")
    _check(tmp_path, "stablelm", StableLmForCausalLM(cfg))


def test_cohere_matches_hf(tmp_path):
    from transformers import CohereConfig, CohereForCausalLM
    torch.manual_seed(0)
    cfg = CohereConfig(hidden_size=64, num_attention_heads=4,
                       num_key_value_heads=4, num_hidden_layers=3,
                       intermediate_size=128, vocab_size=256,
                       logit_scale=0.25, max_position_embeddings=128,
                       attention_dropout=0.0, use_qk_norm=False,
                       torch_dtype="float32")
    app = _check(tmp_path, "cohere", sharpen_attention(CohereForCausalLM(cfg)))
    assert app.spec.block_style == "parallel_shared"
    assert app.spec.rope_interleaved
    assert app.spec.logits_divide == 4.0
