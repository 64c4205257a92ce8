"""Pallas flash attention kernel vs the XLA reference path (interpret mode on
CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.ops import attention as attn
from neuronx_distributed_inference_tpu.ops import flash_attention as fa


def _rand_qkv(rng, b, s, hq, hkv, d, dtype=np.float32):
    q = rng.standard_normal((b, s, hq, d)).astype(dtype)
    k = rng.standard_normal((b, s, hkv, d)).astype(dtype)
    v = rng.standard_normal((b, s, hkv, d)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _xla_ref(q, k, v, scale, window=0, soft_cap=None):
    s = q.shape[1]
    pos = jnp.broadcast_to(jnp.arange(s), (q.shape[0], s))
    mask = attn.prefill_causal_mask(s, pos, window=window)
    return attn.mha(q, k, v, mask, scale, logits_soft_cap=soft_cap)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_flash_matches_xla_causal(rng, hq, hkv):
    b, s, d = 2, 256, 64
    q, k, v = _rand_qkv(rng, b, s, hq, hkv, d)
    scale = d ** -0.5
    ours = fa.flash_attention(q, k, v, scale=scale, block_q=128, block_k=128,
                              interpret=True)
    ref = _xla_ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_sliding_window(rng):
    b, s, d = 1, 256, 64
    q, k, v = _rand_qkv(rng, b, s, 4, 2, d)
    scale = d ** -0.5
    ours = fa.flash_attention(q, k, v, scale=scale, window=100,
                              interpret=True)
    ref = _xla_ref(q, k, v, scale, window=100)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_soft_cap(rng):
    b, s, d = 1, 128, 64
    q, k, v = _rand_qkv(rng, b, s, 4, 4, d)
    scale = d ** -0.5
    ours = fa.flash_attention(q, k, v, scale=scale, soft_cap=30.0,
                              interpret=True)
    ref = _xla_ref(q, k, v, scale, soft_cap=30.0)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_uneven_blocks(rng):
    """block_q != block_k exercises the causal block-skip boundary."""
    b, s, d = 1, 512, 64
    q, k, v = _rand_qkv(rng, b, s, 2, 2, d)
    scale = d ** -0.5
    ours = fa.flash_attention(q, k, v, scale=scale, block_q=256, block_k=128,
                              interpret=True)
    ref = _xla_ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_supports_gate():
    assert fa.supports(512, 64, has_sink=False, chunk=0)
    assert not fa.supports(100, 64, False, 0)     # not block-divisible
    assert not fa.supports(512, 80, False, 0)     # head_dim not 64-multiple
    assert not fa.supports(512, 64, True, 0)      # sink unsupported
    assert not fa.supports(512, 64, False, 128)   # chunked unsupported


def test_model_uses_flash_when_enabled(tmp_path):
    """End-to-end prefill through the model base with flash enabled
    (interpret mode) must match the XLA path."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    from conftest import tiny_llama_hf_config
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)

    torch.manual_seed(0)
    # head_dim must be a 64-multiple for the kernel gate to open
    hf_cfg = tiny_llama_hf_config(max_position_embeddings=512,
                                  hidden_size=256, num_attention_heads=4,
                                  num_key_value_heads=2)
    m = LlamaForCausalLM(LlamaConfig(**hf_cfg))
    m.eval()
    d = tmp_path / "m"
    m.save_pretrained(d, safe_serialization=True)

    def build(flash):
        tcfg = TpuConfig(batch_size=1, seq_len=256, dtype="float32",
                         output_logits=True, enable_bucketing=False,
                         attn_kernel_enabled=flash)
        icfg = LlamaInferenceConfig(tcfg, load_config=load_pretrained_config(str(d)))
        return CausalLMApplication(str(d), icfg, LlamaFamily).load_weights().init_cache()

    ids = np.random.default_rng(0).integers(1, 512, size=(1, 200), dtype=np.int32)
    lens = np.array([200], np.int32)
    # seq bucket = 256 -> block-divisible, flash engages
    out_flash = build(True)._run_prefill(ids, lens)
    out_xla = build(False)._run_prefill(ids, lens)
    np.testing.assert_allclose(np.asarray(out_flash["logits"])[:, :200],
                               np.asarray(out_xla["logits"])[:, :200],
                               atol=2e-4, rtol=2e-4)


def test_flash_prefill_tp4_shard_map(rng):
    """dispatch_prefill shard_maps the kernel over the tp axis; the full
    prefill app output must match the XLA path (the tp=1-only restriction
    of round 3 is lifted)."""
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                                 build_mesh)
    HF = dict(model_type="llama", hidden_size=256, intermediate_size=512,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=64, vocab_size=512,
              rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
              tie_word_embeddings=False, torch_dtype="float32")

    def build(tp, kernel):
        tcfg = TpuConfig(batch_size=2, seq_len=192, dtype="float32",
                         enable_bucketing=True,
                         context_encoding_buckets=[128],
                         tp_degree=tp, attn_kernel_enabled=kernel)
        app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                  LlamaFamily,
                                  mesh=build_mesh(MeshConfig(tp=tp)))
        app.init_random_weights(5).init_cache()
        return app

    ids = np.asarray(rng.integers(1, 500, size=(2, 100)), dtype=np.int64)
    # compare against the XLA path at the SAME tp sharding — cross-tp
    # comparisons flip near-tied greedy tokens through fp32 reduction order
    want = build(4, kernel=False).generate(ids, max_new_tokens=6)
    got = build(4, kernel=True).generate(ids, max_new_tokens=6)
    np.testing.assert_array_equal(got["generated"], want["generated"])


@pytest.mark.parametrize("window", [0, 192])
def test_flash_kernel_dma_elision_index_map_correct(rng, window):
    """The clamped k-block index map must not change results (clamped
    blocks are exactly the skipped ones)."""
    from neuronx_distributed_inference_tpu.ops import attention as attn_ops
    b, s, hq, hkv, d = 1, 512, 2, 1, 64
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    got = fa.flash_attention(q, k, v, scale=d ** -0.5, causal=True,
                             window=window, interpret=True)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    mask = attn_ops.causal_mask(pos, pos, None, window, 0)
    want = attn_ops.mha(q, k, v, mask, d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
