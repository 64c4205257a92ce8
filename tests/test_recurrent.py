"""Golden tests for the recurrent/hybrid state axis (reference:
contrib/models/Falcon-H1-0.5B-Instruct and contrib/models/
recurrentgemma-2b-it — SURVEY §2.7): tiny random-weight HF model vs the
converted app, teacher-forced logits + decisive-margin token equality.
The decode path here exercises the NEW capability: conv tails + SSM /
RG-LRU states carried in the cache pytree across steps (the reference
recomputes the quadratic form every step)."""

import numpy as np
import pytest
import torch

from test_contrib_hub import _check


def test_falcon_h1_matches_hf(tmp_path):
    from transformers import FalconH1Config, FalconH1ForCausalLM
    torch.manual_seed(0)
    cfg = FalconH1Config(
        hidden_size=64, intermediate_size=128, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        max_position_embeddings=128, head_dim=16,
        mamba_d_ssm=48, mamba_n_heads=6, mamba_d_head=8, mamba_n_groups=1,
        mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=8,
        mamba_conv_bias=True, mamba_rms_norm=False,
        torch_dtype="float32")
    app = _check(tmp_path, "falcon_h1", FalconH1ForCausalLM(cfg))
    assert app.spec.ssm is not None and app.spec.ssm_parallel
    assert app.spec.ssm.kind == "mamba2"
    assert app.cache["ssm"].shape == (3, 2, 6, 8, 16)
    assert app.cache["conv_x"].shape == (3, 2, 48, 3)


def test_falcon_h1_mup_and_gated_norm(tmp_path):
    """MuP multipliers folded into weights + the gated-RMSNorm variant +
    an UNTIED checkpoint exercising the untie-at-conversion path."""
    from transformers import FalconH1Config, FalconH1ForCausalLM
    torch.manual_seed(1)
    cfg = FalconH1Config(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        max_position_embeddings=128, head_dim=16,
        mamba_d_ssm=48, mamba_n_heads=6, mamba_d_head=8, mamba_n_groups=2,
        mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=128,
        mamba_conv_bias=True, mamba_rms_norm=True,
        mamba_norm_before_gate=False,
        embedding_multiplier=2.0, lm_head_multiplier=0.5,
        key_multiplier=1.5, attention_in_multiplier=1.25,
        attention_out_multiplier=0.8,
        mlp_multipliers=[1.5, 0.75],
        ssm_multipliers=[1.1, 0.9, 1.2, 0.8, 1.3],
        ssm_in_multiplier=1.5, ssm_out_multiplier=0.7,
        tie_word_embeddings=False,
        torch_dtype="float32")
    app = _check(tmp_path, "falcon_h1", FalconH1ForCausalLM(cfg))
    assert app.spec.ssm.gated_norm
    assert not app.spec.tie_word_embeddings


def test_recurrent_gemma_matches_hf(tmp_path):
    # attention_window_size >= prompt+generation: HF's full forward rolls
    # its key cache mid-prefill once T exceeds the window and misaligns
    # the causal mask against the rolled slots (modeling_recurrent_gemma.py
    # _update_cache), so the teacher-forced golden is only well-defined
    # below the window; the window-crossing behavior is checked against
    # HF's CACHED decode path in test_recurrent_gemma_window_decode
    from transformers import (RecurrentGemmaConfig,
                              RecurrentGemmaForCausalLM)
    torch.manual_seed(0)
    cfg = RecurrentGemmaConfig(
        hidden_size=64, intermediate_size=256, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        lru_width=64, attention_window_size=64, conv1d_width=4,
        vocab_size=256, partial_rotary_factor=0.5,
        block_types=("recurrent", "recurrent", "attention"),
        logits_soft_cap=30.0, torch_dtype="float32")
    app = _check(tmp_path, "recurrent_gemma", RecurrentGemmaForCausalLM(cfg))
    assert app.spec.ssm.kind == "rglru"
    assert app.spec.ssm_pattern == (True, True, False, True)
    # KV rows exist only for the single attention layer
    assert app.cache["k"].shape[0] == 1
    assert app.cache["ssm"].shape == (3, 2, 64)
    assert app.spec.sliding_window == 64


def test_recurrent_gemma_window_decode(tmp_path):
    """Decode across the sliding-window boundary against a torch reference
    with the CORRECT Griffin window mask (attend iff 0 <= q-k < W).

    Neither stock HF path is usable as the golden here: the full-forward
    path rolls its key cache mid-prefill once T > W (mask misaligned with
    the rolled slots), and the cached path shifts one step early at
    pos == W-1, permanently keeping a zero key in the window and dropping
    a real one (transformers 4.57 modeling_recurrent_gemma.py
    _update_cache). So the golden is HF's own modules run full-forward
    with use_cache=False and the causal-mask builder patched to the true
    sliding window."""
    from transformers import (RecurrentGemmaConfig,
                              RecurrentGemmaForCausalLM)
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.family import get_family

    W = 8
    torch.manual_seed(0)
    cfg = RecurrentGemmaConfig(
        hidden_size=64, intermediate_size=256, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        lru_width=64, attention_window_size=W, conv1d_width=4,
        vocab_size=256, partial_rotary_factor=0.5,
        block_types=("recurrent", "recurrent", "attention"),
        logits_soft_cap=30.0, torch_dtype="float32")
    hf = RecurrentGemmaForCausalLM(cfg)
    hf.eval()
    d = tmp_path / "rg_win"
    hf.save_pretrained(d, safe_serialization=True)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, 250, size=(1, 6), dtype=np.int64)
    teach = rng.integers(1, 250, size=(1, 8), dtype=np.int64)
    full = np.concatenate([ids, teach], axis=1)
    T = full.shape[1]

    def windowed_mask(attention_mask, input_tensor, cache_position):
        q = torch.arange(T)[:, None]
        k = torch.arange(T)[None, :]
        allowed = (k <= q) & (q - k < W)
        m = torch.where(allowed, 0.0, torch.finfo(torch.float32).min)
        return m[None, None]

    hf.model._update_causal_mask = windowed_mask
    with torch.no_grad():
        ref = hf(torch.tensor(full), use_cache=False).logits.numpy()

    fam = get_family("recurrent_gemma")
    tcfg = TpuConfig(batch_size=1, seq_len=16, dtype="float32",
                     output_logits=True, enable_bucketing=False)
    app = CausalLMApplication(
        str(d), fam.config_cls(tcfg,
                               load_config=load_pretrained_config(str(d))),
        fam)
    app.load_weights().init_cache()
    res = app.generate(ids.astype(np.int32), max_new_tokens=8,
                       teacher_tokens=teach.astype(np.int32),
                       return_logits=True)
    # decode step i was fed teach[:, i-1] at position 6+i-1 — positions
    # 6..12 cross the window-8 boundary at position 8
    for i in range(1, 8):
        got = np.asarray(res["logits"][i]).reshape(1, -1)
        np.testing.assert_allclose(
            got, ref[:, 6 + i - 1], atol=5e-3, rtol=1e-3,
            err_msg=f"window-crossing decode diverges at step {i}")


def test_recurrent_state_carries_across_decode(tmp_path):
    """The recurrent state must actually matter: zeroing it after prefill
    changes the decoded continuation (guards against a silently-unused
    state cache)."""
    from transformers import FalconH1Config, FalconH1ForCausalLM
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.family import get_family
    import jax.numpy as jnp

    torch.manual_seed(0)
    cfg = FalconH1Config(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        max_position_embeddings=128, head_dim=16,
        mamba_d_ssm=48, mamba_n_heads=6, mamba_d_head=8,
        mamba_d_state=16, torch_dtype="float32")
    d = tmp_path / "fh1"
    m = FalconH1ForCausalLM(cfg)
    m.save_pretrained(d, safe_serialization=True)
    family = get_family("falcon_h1")
    tcfg = TpuConfig(batch_size=1, seq_len=32, dtype="float32",
                     output_logits=True, enable_bucketing=False)
    app = CausalLMApplication(
        str(d), family.config_cls(tcfg,
                                  load_config=load_pretrained_config(str(d))),
        family)
    app.load_weights().init_cache()
    ids = np.arange(1, 9, dtype=np.int64)[None, :]
    pad = np.pad(ids, ((0, 0), (0, 32 - ids.shape[1]))).astype(np.int32)
    lens = np.array([ids.shape[1]], np.int32)
    pos = lens[:, None]

    prefill = app._run_prefill(pad, lens)
    tok = np.asarray(prefill["tokens"]).reshape(1, 1).astype(np.int32)
    conv_before = np.asarray(app.cache["conv_x"]).copy()
    base = np.asarray(app._run_decode(tok, pos)["logits"])
    # decode must advance the conv tail (rolls one slot per step)
    assert np.abs(np.asarray(app.cache["conv_x"]) - conv_before).max() > 1e-6

    # a large injected state must steer the logits (random tiny models have
    # near-zero natural state — A = -(1..nh) decays hard — so injection,
    # not zeroing, is the live-path probe)
    app.reset()
    app._run_prefill(pad, lens)
    app.cache = dict(app.cache)
    app.cache["ssm"] = jnp.ones_like(app.cache["ssm"]) * 10.0
    steered = np.asarray(app._run_decode(tok, pos)["logits"])
    assert np.abs(steered - base).max() > 1e-2, \
        "injected SSM state changed nothing — state read path is dead"


def test_ssm_layer_walk_rejects_residual_spec_knobs():
    """Regression guard: run_layers_ssm hard-codes the plain pre-norm
    residual shape — a hybrid family setting sandwich_norm must fail
    loudly, not run silently wrong. (``residual_multiplier`` was refused
    here too until ISSUE 30 taught the walk to apply it: granitemoehybrid
    sets 0.22 and tests/test_recurrent_paged.py holds its logits.)"""
    import dataclasses

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.models.llama import \
        LlamaInferenceConfig
    from neuronx_distributed_inference_tpu.modules.ssm import SSMSpec

    from conftest import tiny_llama_hf_config

    tcfg = TpuConfig(batch_size=1, seq_len=32, dtype="float32",
                     enable_bucketing=False)
    icfg = LlamaInferenceConfig(tcfg, **tiny_llama_hf_config())
    spec = model_base.spec_from_config(
        icfg, ssm=SSMSpec(kind="mamba2", d_inner=64, num_heads=4, head_dim=16,
                          d_state=16))

    bad = dataclasses.replace(spec, sandwich_norm=True)
    with pytest.raises(NotImplementedError, match="pre-norm residual"):
        model_base.run_layers_ssm(bad, None, None, None, None, None,
                                  None, "prefill")


def test_ssm_layer_walk_applies_the_residual_multiplier(tmp_path):
    """What replaces the refusal: on the CONTIGUOUS prefill + decode path a
    hybrid stack with ``residual_multiplier`` 0.22 (granitemoehybrid: Mamba-2
    mixers interleaved with attention, all four IBM multipliers) gives HF's
    logits, which a walk that ignored the multiplier would miss by far."""
    import json
    import os

    from transformers import (GraniteMoeHybridConfig,
                              GraniteMoeHybridForCausalLM)
    torch.manual_seed(30)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "tests",
            "reference_cases", "granitemoehybrid.json")) as f:
        hf = {k: v for k, v in json.load(f)["config"].items()
              if k != "model_type"}
    assert hf["residual_multiplier"] == 0.22
    model = GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig(
        **hf, torch_dtype="float32")).float()
    app = _check(tmp_path, "granitemoehybrid", model,
                 vocab_hi=hf["vocab_size"])
    assert app.spec.residual_multiplier == 0.22
    assert app.spec.ssm.kind == "mamba2" and not app.spec.ssm_parallel
