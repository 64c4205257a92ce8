"""Golden tests for contrib hub wave 2 (reference: contrib/models/ — SURVEY
§2.7): tiny random-weight HF model vs the converted app, teacher-forced
logits + decisive-margin token equality."""

import numpy as np
import pytest
import torch

from test_contrib_hub import _check, sharpen_attention


def test_gptj_matches_hf(tmp_path):
    from transformers import GPTJConfig, GPTJForCausalLM
    torch.manual_seed(0)
    cfg = GPTJConfig(n_embd=64, n_head=4, n_layer=3, n_positions=128,
                     rotary_dim=8, vocab_size=256, resid_pdrop=0.0,
                     embd_pdrop=0.0, attn_pdrop=0.0, torch_dtype="float32")
    app = _check(tmp_path, "gptj", GPTJForCausalLM(cfg))
    assert app.spec.block_style == "parallel_shared"
    assert app.spec.rope_interleaved and app.spec.rope.rotary_dim == 8
    assert app.spec.lm_head_bias


def test_gpt_neo_matches_hf(tmp_path):
    from transformers import GPTNeoConfig, GPTNeoForCausalLM
    torch.manual_seed(0)
    cfg = GPTNeoConfig(hidden_size=64, num_heads=4, num_layers=4,
                       attention_types=[[["global", "local"], 2]],
                       window_size=8, vocab_size=256,
                       max_position_embeddings=128,
                       resid_dropout=0.0, embed_dropout=0.0,
                       attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "gpt_neo", GPTNeoForCausalLM(cfg))
    assert app.spec.layer_pattern == (False, True, False, True)
    assert app.spec.sliding_window == 8 and app.spec.no_rope
    assert app.spec.attn_scale == 1.0


def test_gpt_bigcode_matches_hf(tmp_path):
    from transformers import GPTBigCodeConfig, GPTBigCodeForCausalLM
    torch.manual_seed(0)
    cfg = GPTBigCodeConfig(n_embd=64, n_head=4, n_layer=3, n_positions=128,
                           multi_query=True, vocab_size=256,
                           resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
                           torch_dtype="float32")
    app = _check(tmp_path, "gpt_bigcode", GPTBigCodeForCausalLM(cfg))
    assert app.spec.num_kv_heads == 1 and app.spec.no_rope


def test_opt_matches_hf(tmp_path):
    from transformers import OPTConfig, OPTForCausalLM
    torch.manual_seed(0)
    cfg = OPTConfig(hidden_size=64, num_attention_heads=4,
                    num_hidden_layers=3, ffn_dim=128, vocab_size=256,
                    max_position_embeddings=128, word_embed_proj_dim=64,
                    do_layer_norm_before=True, dropout=0.0,
                    attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "opt", OPTForCausalLM(cfg))
    assert app.spec.act == "relu" and app.spec.learned_pos == 128


def test_biogpt_matches_hf(tmp_path):
    from transformers import BioGptConfig, BioGptForCausalLM
    torch.manual_seed(0)
    cfg = BioGptConfig(hidden_size=64, num_attention_heads=4,
                       num_hidden_layers=3, intermediate_size=128,
                       vocab_size=256, max_position_embeddings=128,
                       scale_embedding=True, hidden_dropout_prob=0.0,
                       attention_probs_dropout_prob=0.0,
                       activation_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "biogpt", BioGptForCausalLM(cfg))
    assert app.spec.embed_scale == 8.0


def test_xglm_matches_hf(tmp_path):
    from transformers import XGLMConfig, XGLMForCausalLM
    torch.manual_seed(0)
    cfg = XGLMConfig(d_model=64, attention_heads=4, num_layers=3,
                     ffn_dim=128, vocab_size=256,
                     max_position_embeddings=128, dropout=0.0,
                     attention_dropout=0.0, activation_dropout=0.0,
                     layerdrop=0.0, scale_embedding=True,
                     torch_dtype="float32")
    _check(tmp_path, "xglm", XGLMForCausalLM(cfg))


def test_helium_matches_hf(tmp_path):
    from transformers import HeliumConfig, HeliumForCausalLM
    torch.manual_seed(0)
    cfg = HeliumConfig(hidden_size=64, num_attention_heads=4,
                       num_key_value_heads=2, num_hidden_layers=3,
                       intermediate_size=128, head_dim=16, vocab_size=256,
                       attention_dropout=0.0, torch_dtype="float32")
    # fp32 accumulation-order noise reaches ~7e-3 on one logit
    _check(tmp_path, "helium", HeliumForCausalLM(cfg), atol=1.2e-2)


def test_ernie4_5_matches_hf(tmp_path):
    from transformers import Ernie4_5Config, Ernie4_5ForCausalLM
    torch.manual_seed(0)
    # Ernie4_5Config serializes its (True) tie default as null — set it
    cfg = Ernie4_5Config(hidden_size=64, num_attention_heads=4,
                         num_key_value_heads=2, num_hidden_layers=3,
                         intermediate_size=128, vocab_size=256,
                         tie_word_embeddings=True, torch_dtype="float32")
    _check(tmp_path, "ernie4_5", Ernie4_5ForCausalLM(cfg))


def test_seed_oss_matches_hf(tmp_path):
    from transformers import SeedOssConfig, SeedOssForCausalLM
    torch.manual_seed(0)
    cfg = SeedOssConfig(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=2, num_hidden_layers=3,
                        intermediate_size=128, head_dim=16, vocab_size=256,
                        attention_bias=True, attention_dropout=0.0,
                        torch_dtype="float32")
    app = _check(tmp_path, "seed_oss", SeedOssForCausalLM(cfg))
    assert app.spec.qkv_bias


def test_arcee_matches_hf(tmp_path):
    from transformers import ArceeConfig, ArceeForCausalLM
    torch.manual_seed(0)
    cfg = ArceeConfig(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, num_hidden_layers=3,
                      intermediate_size=128, vocab_size=256,
                      hidden_act="relu2", torch_dtype="float32")
    app = _check(tmp_path, "arcee", ArceeForCausalLM(cfg))
    assert app.spec.act == "relu2" and not app.spec.mlp_glu


def test_nemotron_matches_hf(tmp_path):
    from transformers import NemotronConfig, NemotronForCausalLM
    torch.manual_seed(0)
    cfg = NemotronConfig(hidden_size=64, num_attention_heads=4,
                         num_key_value_heads=2, num_hidden_layers=3,
                         intermediate_size=128, vocab_size=256,
                         hidden_act="relu2", partial_rotary_factor=0.5,
                         attention_dropout=0.0, hidden_dropout=0.0,
                         torch_dtype="float32")
    app = _check(tmp_path, "nemotron", NemotronForCausalLM(cfg))
    assert app.spec.rope.rotary_dim == 8 and app.spec.norm_type == "layernorm"


def test_smollm3_matches_hf(tmp_path):
    from transformers import SmolLM3Config, SmolLM3ForCausalLM
    torch.manual_seed(0)
    cfg = SmolLM3Config(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=2, num_hidden_layers=4,
                        intermediate_size=128, vocab_size=256,
                        pad_token_id=0, no_rope_layer_interval=2,
                        tie_word_embeddings=True, attention_dropout=0.0,
                        torch_dtype="float32")
    app = _check(tmp_path, "smollm3", SmolLM3ForCausalLM(cfg))
    assert app.spec.layer_pattern is not None and app.spec.nope_global


def test_cohere2_matches_hf(tmp_path):
    from transformers import Cohere2Config, Cohere2ForCausalLM
    torch.manual_seed(0)
    cfg = Cohere2Config(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=2, num_hidden_layers=4,
                        intermediate_size=128, vocab_size=256,
                        sliding_window=8, sliding_window_pattern=2,
                        layer_types=["sliding_attention", "full_attention",
                                     "sliding_attention", "full_attention"],
                        logit_scale=0.25, attention_dropout=0.0,
                        torch_dtype="float32")
    app = _check(tmp_path, "cohere2",
                 sharpen_attention(Cohere2ForCausalLM(cfg)))
    assert app.spec.layer_pattern == (True, False, True, False)
    assert app.spec.rope_interleaved
    assert app.spec.block_style == "parallel_shared" and app.spec.nope_global


def test_exaone4_matches_hf(tmp_path):
    from transformers import Exaone4Config, Exaone4ForCausalLM
    torch.manual_seed(0)
    cfg = Exaone4Config(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=2, num_hidden_layers=4,
                        intermediate_size=128, head_dim=16, vocab_size=256,
                        sliding_window=8,
                        layer_types=["sliding_attention", "sliding_attention",
                                     "sliding_attention", "full_attention"],
                        attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "exaone4", Exaone4ForCausalLM(cfg))
    assert app.spec.norm_position == "post" and app.spec.qk_norm
    assert app.spec.layer_pattern == (True, True, True, False)


def test_hunyuan_dense_matches_hf(tmp_path):
    from transformers import HunYuanDenseV1Config, HunYuanDenseV1ForCausalLM
    torch.manual_seed(0)
    cfg = HunYuanDenseV1Config(hidden_size=64, num_attention_heads=4,
                               num_key_value_heads=2, num_hidden_layers=3,
                               intermediate_size=128, head_dim=16,
                               vocab_size=256, attention_dropout=0.0,
                               torch_dtype="float32")
    app = _check(tmp_path, "hunyuan_v1_dense",
                 HunYuanDenseV1ForCausalLM(cfg))
    assert app.spec.qk_norm and app.spec.qk_norm_after_rope


def test_granitemoe_matches_hf(tmp_path):
    from transformers import GraniteMoeConfig, GraniteMoeForCausalLM
    torch.manual_seed(0)
    cfg = GraniteMoeConfig(hidden_size=64, num_attention_heads=4,
                           num_key_value_heads=2, num_hidden_layers=3,
                           intermediate_size=64, vocab_size=256,
                           num_local_experts=4, num_experts_per_tok=2,
                           embedding_multiplier=2.0, logits_scaling=2.0,
                           residual_multiplier=0.5,
                           attention_multiplier=0.25,
                           attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "granitemoe", GraniteMoeForCausalLM(cfg))
    assert app.spec.moe is not None and app.spec.moe.pre_softmax_topk


def test_olmoe_matches_hf(tmp_path):
    from transformers import OlmoeConfig, OlmoeForCausalLM
    torch.manual_seed(0)
    cfg = OlmoeConfig(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, num_hidden_layers=3,
                      intermediate_size=32, vocab_size=256,
                      num_experts=4, num_experts_per_tok=2,
                      norm_topk_prob=False, attention_dropout=0.0,
                      torch_dtype="float32")
    app = _check(tmp_path, "olmoe", OlmoeForCausalLM(cfg))
    assert app.spec.qk_norm_full and app.spec.moe is not None
    assert not app.spec.moe.normalize_topk


def test_glm4_moe_matches_hf(tmp_path):
    from transformers import Glm4MoeConfig, Glm4MoeForCausalLM
    torch.manual_seed(0)
    cfg = Glm4MoeConfig(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=2, num_hidden_layers=3,
                        intermediate_size=64, moe_intermediate_size=32,
                        head_dim=16, vocab_size=256,
                        n_routed_experts=4, num_experts_per_tok=2,
                        n_shared_experts=1, first_k_dense_replace=1,
                        n_group=1, topk_group=1, norm_topk_prob=True,
                        use_qk_norm=True, attention_bias=True,
                        partial_rotary_factor=0.5, attention_dropout=0.0,
                        torch_dtype="float32")
    app = _check(tmp_path, "glm4_moe", Glm4MoeForCausalLM(cfg))
    assert app.spec.first_dense == 1 and app.spec.qk_norm
    assert app.spec.moe.router_act == "sigmoid"
    assert app.spec.moe.shared_intermediate == 32


def test_bloom_matches_hf(tmp_path):
    from transformers import BloomConfig, BloomForCausalLM
    torch.manual_seed(0)
    cfg = BloomConfig(hidden_size=64, n_head=4, n_layer=3, vocab_size=256,
                      hidden_dropout=0.0, attention_dropout=0.0,
                      torch_dtype="float32")
    app = _check(tmp_path, "bloom", BloomForCausalLM(cfg))
    assert app.spec.alibi and app.spec.embed_norm and app.spec.no_rope


def test_mpt_matches_hf(tmp_path):
    from transformers import MptConfig, MptForCausalLM
    torch.manual_seed(0)
    cfg = MptConfig(d_model=64, n_heads=4, n_layers=3, vocab_size=256,
                    torch_dtype="float32")
    cfg.attn_config.attn_pdrop = 0.0
    app = _check(tmp_path, "mpt", MptForCausalLM(cfg))
    assert app.spec.alibi and not app.spec.mlp_bias


def test_alibi_slopes_match_hf():
    """Slope formulas must reproduce HF's build_alibi_tensor /
    build_mpt_alibi_tensor exactly, incl. non-power-of-two head counts."""
    import math
    import torch as th
    from transformers.models.bloom.modeling_bloom import build_alibi_tensor
    from transformers.models.mpt.modeling_mpt import build_mpt_alibi_tensor
    from neuronx_distributed_inference_tpu.ops.attention import alibi_slopes
    for h in (4, 8, 6, 12):
        mask = th.ones((1, 5))
        ref = build_alibi_tensor(mask, h, th.float32)     # (h, 1, 5)
        ref_slopes = (ref.view(h, 5)[:, 1] - ref.view(h, 5)[:, 0]).numpy()
        np.testing.assert_allclose(alibi_slopes(h, "bloom"), ref_slopes,
                                   rtol=1e-6)
        ref2 = build_mpt_alibi_tensor(h, 5)               # (1, h, 1, 5)
        ref2_slopes = (ref2.view(h, 5)[:, -1]
                       - ref2.view(h, 5)[:, -2]).numpy()
        np.testing.assert_allclose(alibi_slopes(h, "mpt"), ref2_slopes,
                                   rtol=1e-5)


def test_persimmon_matches_hf(tmp_path):
    from transformers import PersimmonConfig, PersimmonForCausalLM
    torch.manual_seed(0)
    cfg = PersimmonConfig(hidden_size=64, num_attention_heads=4,
                          num_hidden_layers=3, intermediate_size=128,
                          vocab_size=256, qk_layernorm=True,
                          partial_rotary_factor=0.5,
                          hidden_act="relu2", attention_dropout=0.0,
                          hidden_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "persimmon", PersimmonForCausalLM(cfg))
    assert app.spec.qk_norm and app.spec.qk_norm_type == "layernorm"
    assert app.spec.rope.rotary_dim == 8


def test_dots1_matches_hf(tmp_path):
    from transformers import Dots1Config, Dots1ForCausalLM
    torch.manual_seed(0)
    cfg = Dots1Config(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, num_hidden_layers=3,
                      intermediate_size=64, moe_intermediate_size=32,
                      head_dim=16, vocab_size=256,
                      n_routed_experts=4, num_experts_per_tok=2,
                      n_shared_experts=1, first_k_dense_replace=1,
                      n_group=1, topk_group=1, norm_topk_prob=True,
                      routed_scaling_factor=1.0,
                      attention_dropout=0.0, torch_dtype="float32")
    app = _check(tmp_path, "dots1", Dots1ForCausalLM(cfg))
    assert app.spec.qk_norm and app.spec.moe.router_act == "sigmoid"
    assert app.spec.first_dense == 1


def test_codegen_matches_hf(tmp_path):
    from transformers import CodeGenConfig, CodeGenForCausalLM
    torch.manual_seed(0)
    cfg = CodeGenConfig(n_embd=64, n_head=4, n_layer=3, n_positions=128,
                        rotary_dim=8, vocab_size=256, resid_pdrop=0.0,
                        embd_pdrop=0.0, attn_pdrop=0.0,
                        torch_dtype="float32")
    app = _check(tmp_path, "codegen", CodeGenForCausalLM(cfg))
    assert app.spec.block_style == "parallel_shared"
    assert app.spec.rope_interleaved
