"""Golden tests for contrib hub wave 3 (reference: contrib/models/ —
SURVEY §2.7): openai-gpt (post-LN), LFM2 (hybrid short-conv), VaultGemma,
Apertus (xIELU), Phi-3.5-MoE (sparsemixer)."""

import numpy as np
import pytest
import torch

from test_contrib_hub import _check


def test_openai_gpt_matches_hf(tmp_path):
    from transformers import OpenAIGPTConfig, OpenAIGPTLMHeadModel
    torch.manual_seed(0)
    cfg = OpenAIGPTConfig(n_embd=64, n_head=4, n_layer=3, n_positions=128,
                          vocab_size=256, resid_pdrop=0.0, embd_pdrop=0.0,
                          attn_pdrop=0.0, torch_dtype="float32")
    app = _check(tmp_path, "openai-gpt", OpenAIGPTLMHeadModel(cfg))
    assert app.spec.norm_position == "post_residual"
    assert app.spec.skip_final_norm and app.spec.no_rope


def _lfm2_toy():
    from transformers import Lfm2Config
    return Lfm2Config(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, num_hidden_layers=4,
                      intermediate_size=128, vocab_size=256,
                      layer_types=["conv", "conv", "full_attention", "conv"],
                      conv_L_cache=3, conv_bias=False,
                      block_auto_adjust_ff_dim=False,
                      max_position_embeddings=128, torch_dtype="float32")


def test_lfm2_matches_hf(tmp_path):
    from transformers import Lfm2ForCausalLM
    torch.manual_seed(0)
    cfg = _lfm2_toy()
    app = _check(tmp_path, "lfm2", Lfm2ForCausalLM(cfg))
    assert app.spec.ssm.kind == "shortconv"
    assert app.spec.ssm_pattern == (True, True, False, True)
    assert app.cache["k"].shape[0] == 1          # one attention layer
    assert app.cache["conv_x"].shape == (3, 2, 2, 64)      # time-major
    assert "ssm" not in app.cache                # conv state only


def test_lfm2_serves_paged_against_hf(tmp_path):
    """ISSUE 61: with the short convolution continuing from a carried tail
    the dense sibling serves through the paged path too: a prompt walked in
    chunks (16 + 16 + a padded 5) through ``PagedEngineAdapter`` with
    default arguments, then decode through the pool and the state slots,
    against transformers' ``Lfm2ForCausalLM`` on the same weights."""
    from transformers import Lfm2ForCausalLM

    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from test_recurrent_paged import LogitTap
    torch.manual_seed(0)
    cfg = _lfm2_toy()
    hf = Lfm2ForCausalLM(cfg).eval()
    d = tmp_path / "lfm2"
    hf.save_pretrained(d, safe_serialization=True)
    family = get_family("lfm2")
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     output_logits=True, is_block_kv_layout=True,
                     pa_block_size=8, pa_num_blocks=16,
                     context_encoding_buckets=[8, 16], enable_bucketing=True)
    app = PagedCausalLMApplication(
        str(d), family.config_cls(
            tcfg, load_config=load_pretrained_config(str(d))), family)
    app.load_weights().init_cache()
    assert app.cache["conv_x"].shape == (3, 2, 2, 64)
    prompt = np.random.default_rng(3).integers(1, 250, size=37).tolist()
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = [ad.add_requests([5], [prompt])[5]]
    for _ in range(6):
        stream.append(ad.step([5])[5])
    assert tap.shapes == [(1, 16), (1, 16), (1, 8)] + [(2, 1)] * 6
    fed = prompt + stream[:-1]
    with torch.no_grad():
        want = hf(torch.tensor([fed])).logits[0].numpy()
    np.testing.assert_allclose(tap.logits(5, len(fed))[:, :256], want,
                               atol=2e-4)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def test_lfm2_conv_bias_and_auto_ff(tmp_path):
    from transformers import Lfm2Config, Lfm2ForCausalLM
    torch.manual_seed(1)
    cfg = Lfm2Config(hidden_size=64, num_attention_heads=4,
                     num_key_value_heads=2, num_hidden_layers=2,
                     intermediate_size=96, vocab_size=256,
                     layer_types=["conv", "full_attention"],
                     conv_L_cache=4, conv_bias=True,
                     block_auto_adjust_ff_dim=True,
                     block_multiple_of=16, block_ffn_dim_multiplier=1.0,
                     max_position_embeddings=128, torch_dtype="float32")
    app = _check(tmp_path, "lfm2", Lfm2ForCausalLM(cfg))
    # 2*96/3 = 64 rounded up to multiple of 16
    assert app.spec.intermediate_size == 64
    assert app.spec.ssm.conv_bias


def test_vaultgemma_matches_hf(tmp_path):
    from transformers import VaultGemmaConfig, VaultGemmaForCausalLM
    torch.manual_seed(0)
    cfg = VaultGemmaConfig(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_hidden_layers=4, intermediate_size=128,
        vocab_size=256, sliding_window=16, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, query_pre_attn_scalar=16,
        layer_types=["sliding_attention", "full_attention"] * 2,
        max_position_embeddings=128, torch_dtype="float32")
    app = _check(tmp_path, "vaultgemma", VaultGemmaForCausalLM(cfg))
    assert app.spec.layer_pattern == (True, False, True, False)
    assert app.spec.attn_soft_cap == 50.0
    assert app.spec.norm_offset == 1.0 and not app.spec.sandwich_norm


def test_apertus_matches_hf(tmp_path):
    from transformers import ApertusConfig, ApertusForCausalLM
    torch.manual_seed(0)
    cfg = ApertusConfig(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=2, num_hidden_layers=3,
                        intermediate_size=128, vocab_size=256,
                        max_position_embeddings=128,
                        torch_dtype="float32")
    app = _check(tmp_path, "apertus", ApertusForCausalLM(cfg))
    assert app.spec.act == "xielu" and not app.spec.mlp_glu
    assert app.spec.qk_norm


def test_phimoe_matches_hf(tmp_path):
    from transformers.models.phimoe import PhimoeConfig, PhimoeForCausalLM
    torch.manual_seed(0)
    cfg = PhimoeConfig(hidden_size=64, num_attention_heads=4,
                       num_key_value_heads=2, num_hidden_layers=2,
                       intermediate_size=96, vocab_size=256,
                       num_local_experts=4, num_experts_per_tok=2,
                       router_jitter_noise=0.01, input_jitter_noise=0.0,
                       attention_bias=True, lm_head_bias=True,
                       max_position_embeddings=128,
                       tie_word_embeddings=False, torch_dtype="float32")
    app = _check(tmp_path, "phimoe", PhimoeForCausalLM(cfg))
    assert app.spec.moe.router_act == "sparsemixer"
    assert app.spec.norm_type == "layernorm" and app.spec.norm_bias
    assert app.spec.lm_head_bias


def test_olmo3_matches_hf(tmp_path):
    from transformers import Olmo3Config, Olmo3ForCausalLM
    torch.manual_seed(0)
    cfg = Olmo3Config(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, num_hidden_layers=4,
                      intermediate_size=128, vocab_size=256,
                      sliding_window=8,
                      layer_types=["sliding_attention", "sliding_attention",
                                   "sliding_attention", "full_attention"],
                      max_position_embeddings=128, torch_dtype="float32")
    app = _check(tmp_path, "olmo3", Olmo3ForCausalLM(cfg))
    assert app.spec.qk_norm_full and app.spec.norm_position == "post"
    assert app.spec.layer_pattern == (True, True, True, False)
    assert app.spec.sliding_window == 8


def _llama_sd_and_cfg(rng_seed=0, **kw):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(rng_seed)
    cfg = LlamaConfig(hidden_size=64, intermediate_size=128,
                      num_hidden_layers=3, num_attention_heads=4,
                      num_key_value_heads=2, vocab_size=256,
                      max_position_embeddings=128, torch_dtype="float32",
                      **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def test_minicpm_matches_scaled_llama(tmp_path):
    """MiniCPM is llama + three scalings (reference: contrib/models/
    MiniCPM4-8B/src/modeling_minicpm.py). Golden: a torch llama whose
    weights carry the scalings folded in — embed x scale_emb, o/down_proj
    x scale_depth/sqrt(L), lm_head / (H/dim_model_base) — must equal our
    minicpm app running the UNscaled weights with the config knobs."""
    import json
    import torch as th
    from transformers import LlamaForCausalLM
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu.utils.testing import \
        check_generation_golden

    m, cfg = _llama_sd_and_cfg()
    scale_emb, scale_depth, dmb = 4.0, 1.4, 32
    L, H = cfg.num_hidden_layers, cfg.hidden_size
    rm = scale_depth / np.sqrt(L)

    golden = LlamaForCausalLM(cfg)
    golden.load_state_dict(m.state_dict())
    with th.no_grad():
        golden.model.embed_tokens.weight.mul_(scale_emb)
        golden.lm_head.weight.mul_(1.0 / (H / dmb))
        for lyr in golden.model.layers:
            lyr.self_attn.o_proj.weight.mul_(rm)
            lyr.mlp.down_proj.weight.mul_(rm)
    golden.eval()
    golden.generation_config.eos_token_id = None

    d = tmp_path / "minicpm"
    m.save_pretrained(d, safe_serialization=True)
    # rewrite config.json as a minicpm config with the scaling knobs
    cj = json.load(open(d / "config.json"))
    cj.update(model_type="minicpm", scale_emb=scale_emb,
              scale_depth=scale_depth, dim_model_base=dmb)
    json.dump(cj, open(d / "config.json", "w"))

    family = get_family("minicpm")
    tcfg = TpuConfig(batch_size=2, seq_len=48, dtype="float32",
                     output_logits=True, enable_bucketing=False)
    app = CausalLMApplication(
        str(d), family.config_cls(tcfg,
                                  load_config=load_pretrained_config(str(d))),
        family)
    app.load_weights().init_cache()
    assert app.spec.embed_scale == scale_emb
    assert abs(app.spec.logits_divide - H / dmb) < 1e-9
    ids = np.random.default_rng(0).integers(1, 250, size=(2, 12),
                                            dtype=np.int64)
    check_generation_golden(app, ids, golden, max_new_tokens=8, atol=6e-3)


def test_orion_matches_renamed_stablelm(tmp_path):
    """Orion is llama-with-LayerNorm (reference: contrib/models/
    orion-14b-chat/src/modeling_orion.py) — structurally identical to
    stablelm at rotary_pct=1.0 without biases; a stablelm checkpoint
    renamed to orion's names is the golden."""
    from transformers import StableLmConfig, StableLmForCausalLM
    torch.manual_seed(0)
    cfg = StableLmConfig(hidden_size=64, intermediate_size=128,
                         num_hidden_layers=3, num_attention_heads=4,
                         num_key_value_heads=2, vocab_size=256,
                         rope_pct=1.0, partial_rotary_factor=1.0,
                         use_qkv_bias=False, use_parallel_residual=False,
                         max_position_embeddings=128, torch_dtype="float32")
    hf = StableLmForCausalLM(cfg)
    hf.eval()
    import json
    d = tmp_path / "orion"
    hf.save_pretrained(d, safe_serialization=True)
    cj = json.load(open(d / "config.json"))
    cj["model_type"] = "orion"
    json.dump(cj, open(d / "config.json", "w"))

    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu.utils.testing import \
        check_generation_golden
    hf.generation_config.eos_token_id = None
    family = get_family("orion")
    tcfg = TpuConfig(batch_size=2, seq_len=48, dtype="float32",
                     output_logits=True, enable_bucketing=False)
    app = CausalLMApplication(
        str(d), family.config_cls(tcfg,
                                  load_config=load_pretrained_config(str(d))),
        family)
    app.load_weights().init_cache()
    assert app.spec.norm_type == "layernorm" and app.spec.norm_bias
    ids = np.random.default_rng(0).integers(1, 250, size=(2, 12),
                                            dtype=np.int64)
    check_generation_golden(app, ids, hf, max_new_tokens=8, atol=6e-3)


def test_internlm3_matches_qwen2_weights(tmp_path):
    """InternLM3 is llama + qkv biases (reference: contrib/models/
    internlm3-8b-instruct/src/modeling_internlm3.py) — structurally qwen2;
    a qwen2 checkpoint with internlm3's config knobs is the golden."""
    import json
    from transformers import Qwen2Config, Qwen2ForCausalLM
    torch.manual_seed(0)
    cfg = Qwen2Config(hidden_size=64, intermediate_size=128,
                      num_hidden_layers=3, num_attention_heads=4,
                      num_key_value_heads=2, vocab_size=256,
                      max_position_embeddings=128, torch_dtype="float32")
    hf = Qwen2ForCausalLM(cfg)
    hf.eval()
    d = tmp_path / "internlm3"
    hf.save_pretrained(d, safe_serialization=True)
    cj = json.load(open(d / "config.json"))
    cj.update(model_type="internlm3", qkv_bias=True, bias=False)
    json.dump(cj, open(d / "config.json", "w"))

    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu.utils.testing import \
        check_generation_golden
    hf.generation_config.eos_token_id = None
    family = get_family("internlm3")
    tcfg = TpuConfig(batch_size=2, seq_len=48, dtype="float32",
                     output_logits=True, enable_bucketing=False)
    app = CausalLMApplication(
        str(d), family.config_cls(tcfg,
                                  load_config=load_pretrained_config(str(d))),
        family)
    app.load_weights().init_cache()
    assert app.spec.qkv_bias and not app.spec.o_bias
    ids = np.random.default_rng(0).integers(1, 250, size=(2, 12),
                                            dtype=np.int64)
    check_generation_golden(app, ids, hf, max_new_tokens=8, atol=6e-3)


def test_longrope_scaling():
    """longrope (phi-3/minicpm4): per-slot factors + the sqrt-log attention
    factor when deployed context exceeds the original."""
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.ops.rope import (RopeConfig,
                                                            rope_cos_sin)
    short = tuple(1.0 for _ in range(8))
    long = tuple(2.0 for _ in range(8))
    pos = np.arange(6)[None, :]
    base = RopeConfig(head_dim=16)
    c0, _ = rope_cos_sin(jnp.asarray(pos), base)
    # short regime (max_position == original): factors 1.0 -> plain rope
    cfg_s = RopeConfig(head_dim=16, scaling_type="longrope",
                       short_factor=short, long_factor=long,
                       original_max_position=128, max_position=128)
    c1, _ = rope_cos_sin(jnp.asarray(pos), cfg_s)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), atol=1e-6)
    # long regime: halved frequencies + amplitude factor
    cfg_l = RopeConfig(head_dim=16, scaling_type="longrope",
                       short_factor=short, long_factor=long,
                       original_max_position=128, max_position=512)
    c2, _ = rope_cos_sin(jnp.asarray(pos), cfg_l)
    f = np.sqrt(1 + np.log(4) / np.log(128))
    got = np.asarray(c2)[0, 2, 0]
    want = np.cos(2 * 1.0 / 2.0) * f
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_phi3_longrope_matches_hf(tmp_path):
    """phi-3 longrope (su) scaling: per-slot long factors + the sqrt-log
    attention factor must match HF when the deployed context exceeds the
    original pretraining length (original_max_position_embeddings lives at
    the TOP level of the phi3 config)."""
    from transformers import Phi3Config, Phi3ForCausalLM
    torch.manual_seed(0)
    d2 = 8    # head_dim 16 -> 8 freq slots
    cfg = Phi3Config(hidden_size=64, intermediate_size=128,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, vocab_size=256,
                     max_position_embeddings=256,
                     original_max_position_embeddings=64,
                     rope_scaling={"type": "longrope",
                                   "short_factor": [1.0] * d2,
                                   "long_factor": [1.5] * d2},
                     pad_token_id=0, bos_token_id=1, eos_token_id=2,
                     torch_dtype="float32")
    app = _check(tmp_path, "phi3", Phi3ForCausalLM(cfg))
    assert app.spec.rope.scaling_type == "longrope"
    assert app.spec.rope.original_max_position == 64
    assert app.spec.rope.long_factor == (1.5,) * d2


def test_ministral_matches_hf(tmp_path):
    from transformers import MinistralConfig, MinistralForCausalLM
    torch.manual_seed(0)
    cfg = MinistralConfig(hidden_size=64, intermediate_size=128,
                          num_hidden_layers=3, num_attention_heads=4,
                          num_key_value_heads=2, vocab_size=256,
                          sliding_window=8, head_dim=16,
                          max_position_embeddings=128,
                          torch_dtype="float32")
    app = _check(tmp_path, "ministral", MinistralForCausalLM(cfg))
    assert app.spec.sliding_window == 8
