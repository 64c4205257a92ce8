"""Correctness tests for the Pallas decode (TKG) attention kernel
(``ops/decode_attention.py``) against the XLA reference path
(``ops/attention.mha``), run in Pallas interpret mode on CPU
(reference test analog: unit kernel tests, SURVEY §4 tier 1).

Covers GQA grouping, per-row live lengths, sliding window, learned sink,
soft-cap, stacked-cache layer addressing, and multi-block grids
(block_s < S, forcing the DMA-elision index-map path)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from neuronx_distributed_inference_tpu.ops import attention as attn_ops
from neuronx_distributed_inference_tpu.ops import decode_attention as da


def _reference(q, k_cache, v_cache, new_k, new_v, lens, scale,
               window=0, soft_cap=None, sink=None):
    """XLA-path reference: write the active token at row position, attend
    with the decode mask over the full cache (what model_base._layer_body
    does on the non-kernel branch). Caches arrive in the native layouts —
    K transposed (B,Hkv,D,S), V (B,Hkv,S,D) — and are viewed (B,S,Hkv,D)
    for the mha reference."""
    k_cache = np.asarray(jnp.transpose(k_cache, (0, 3, 1, 2)))  # (B,S,Hkv,D)
    v_cache = np.asarray(jnp.swapaxes(v_cache, 1, 2))
    b, s = k_cache.shape[0], k_cache.shape[1]
    rows = np.arange(b)
    k_full = np.array(k_cache)
    v_full = np.array(v_cache)
    k_full[rows, np.array(lens)] = np.array(new_k)
    v_full[rows, np.array(lens)] = np.array(new_v)
    positions = jnp.asarray(lens)[:, None]          # (B, 1)
    mask = attn_ops.decode_mask(positions, s, window=window)
    out = attn_ops.mha(q[:, None], jnp.asarray(k_full), jnp.asarray(v_full),
                       mask, scale, logits_soft_cap=soft_cap, sink=sink)
    return out[:, 0]


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _run_kernel(q, kc, vc, nk, nv, lens, scale, window=0, soft_cap=None,
                sink=None, block_s=64):
    return da.decode_attention(
        q, kc, vc, nk, nv, jnp.asarray(lens, jnp.int32), scale=scale,
        window=window, soft_cap=soft_cap, sink=sink, block_s=block_s,
        interpret=True)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (4, 1)])
def test_decode_attention_gqa_matches_xla(rng, hq, hkv):
    b, s, d = 3, 256, 64
    lens = np.array([5, 130, 255], np.int32)
    q = _rand(rng, b, hq, d)
    kc = _rand(rng, b, hkv, d, s)
    vc = _rand(rng, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    scale = d ** -0.5
    got = _run_kernel(q, kc, vc, nk, nv, lens, scale)
    want = _reference(q, kc, vc, nk, nv, lens, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_zero_len_row(rng):
    """A fresh row (lens=0) attends only to its own active token."""
    b, s, hq, hkv, d = 2, 128, 4, 2, 64
    lens = np.array([0, 64], np.int32)
    q = _rand(rng, b, hq, d)
    kc = _rand(rng, b, hkv, d, s)
    vc = _rand(rng, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    got = _run_kernel(q, kc, vc, nk, nv, lens, d ** -0.5)
    want = _reference(q, kc, vc, nk, nv, lens, d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 100])
def test_decode_attention_sliding_window(rng, window):
    b, s, hq, hkv, d = 2, 256, 4, 2, 64
    lens = np.array([200, 255], np.int32)
    q = _rand(rng, b, hq, d)
    kc = _rand(rng, b, hkv, d, s)
    vc = _rand(rng, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    got = _run_kernel(q, kc, vc, nk, nv, lens, d ** -0.5, window=window)
    want = _reference(q, kc, vc, nk, nv, lens, d ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_sink(rng):
    b, s, hq, hkv, d = 2, 128, 4, 2, 64
    lens = np.array([60, 100], np.int32)
    q = _rand(rng, b, hq, d)
    kc = _rand(rng, b, hkv, d, s)
    vc = _rand(rng, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    sink = _rand(rng, hq)
    got = _run_kernel(q, kc, vc, nk, nv, lens, d ** -0.5, sink=sink)
    want = _reference(q, kc, vc, nk, nv, lens, d ** -0.5, sink=sink)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_soft_cap(rng):
    b, s, hq, hkv, d = 2, 128, 4, 2, 64
    lens = np.array([60, 100], np.int32)
    q = _rand(rng, b, hq, d)
    kc = _rand(rng, b, hkv, d, s)
    vc = _rand(rng, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    got = _run_kernel(q, kc, vc, nk, nv, lens, d ** -0.5, soft_cap=30.0)
    want = _reference(q, kc, vc, nk, nv, lens, d ** -0.5, soft_cap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_stacked_layer_addressing(rng):
    """The stacked variant must read layer ``li`` out of (L,B,S,Hkv,D)."""
    L, b, s, hq, hkv, d = 3, 2, 128, 4, 2, 64
    lens = np.array([50, 90], np.int32)
    q = _rand(rng, b, hq, d)
    kcs = _rand(rng, L, b, hkv, d, s)
    vcs = _rand(rng, L, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    scale = d ** -0.5
    for li in range(L):
        got = da.decode_attention_stacked(
            q, kcs, vcs, nk, nv, jnp.asarray(li, jnp.int32),
            jnp.asarray(lens, jnp.int32), scale=scale, block_s=64,
            interpret=True)
        want = _reference(q, kcs[li], vcs[li], nk, nv, lens, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"layer {li}")


def test_decode_attention_dynamic_window_per_layer(rng):
    """window is a traced scalar — the gemma3/gpt-oss alternating pattern
    passes a different window per layer through one scan body."""
    b, s, hq, hkv, d = 2, 256, 4, 2, 64
    lens = np.array([200, 255], np.int32)
    q = _rand(rng, b, hq, d)
    kc = _rand(rng, b, hkv, d, s)
    vc = _rand(rng, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    scale = d ** -0.5
    for w in (0, 64):
        got = da.decode_attention_stacked(
            q, kc[None], vc[None], nk, nv, jnp.asarray(0, jnp.int32),
            jnp.asarray(lens, jnp.int32), scale=scale,
            window=jnp.asarray(w, jnp.int32), block_s=64, interpret=True)
        want = _reference(q, kc, vc, nk, nv, lens, scale, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"window {w}")


def _kernel_app(ckpt, tp, enabled, tmp_name=None):
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                                 build_mesh)
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     output_logits=True, enable_bucketing=False, tp_degree=tp,
                     attn_block_tkg_kernel_enabled=enabled)
    icfg = LlamaInferenceConfig(tcfg, load_config=load_pretrained_config(ckpt))
    app = CausalLMApplication(ckpt, icfg, LlamaFamily,
                              mesh=build_mesh(MeshConfig(tp=tp)))
    app.load_weights()
    app.init_cache()
    return app


@pytest.fixture(scope="module")
def hd64_ckpt(tmp_path_factory):
    """Tiny llama with head_dim=64 — the decode kernel's admission shape
    (supports() requires head_dim 64/128; the shared tiny config's
    head_dim=16 never routes through it)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    from conftest import tiny_llama_hf_config
    torch.manual_seed(0)
    cfg = LlamaConfig(**tiny_llama_hf_config(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=512, num_hidden_layers=2))
    model = LlamaForCausalLM(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("tiny_llama_hd64")
    model.save_pretrained(d, safe_serialization=True)
    return str(d)


def test_decode_kernel_e2e_matches_xla_path(hd64_ckpt):
    """Full application decode with the Pallas kernel (default-on) must
    reproduce the XLA-path tokens and logits."""
    from neuronx_distributed_inference_tpu.models import model_base
    prompts = np.random.default_rng(7).integers(
        1, 500, size=(2, 12)).astype(np.int32)
    app_k = _kernel_app(hd64_ckpt, tp=1, enabled=True)
    assert app_k.spec.decode_kernel and app_k.spec.head_dim == 64
    out_k = app_k.generate(prompts, max_new_tokens=8, return_logits=True)
    app_x = _kernel_app(hd64_ckpt, tp=1, enabled=False)
    assert not app_x.spec.decode_kernel
    out_x = app_x.generate(prompts, max_new_tokens=8, return_logits=True)
    np.testing.assert_array_equal(out_k["generated"], out_x["generated"])
    for a, b in zip(out_k["logits"], out_x["logits"]):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)


def test_decode_kernel_e2e_tp8_shard_map(hd64_ckpt):
    """tp=8 on the virtual CPU mesh: kv heads replicate 2->8 (GQA), the
    dispatch shard_maps the kernel over the tp axis; output must match the
    single-device XLA path."""
    prompts = np.random.default_rng(7).integers(
        1, 500, size=(2, 12)).astype(np.int32)
    out_ref = _kernel_app(hd64_ckpt, tp=1, enabled=False).generate(
        prompts, max_new_tokens=8, return_logits=True)
    app = _kernel_app(hd64_ckpt, tp=8, enabled=True)
    assert app.spec.decode_kernel
    out = app.generate(prompts, max_new_tokens=8, return_logits=True)
    np.testing.assert_array_equal(out["generated"], out_ref["generated"])
    for a, b in zip(out["logits"], out_ref["logits"]):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=1e-3)


def test_decode_kv_view_bucketing_matches_full(hd64_ckpt):
    """TKG seq buckets: the decode graph reads only cache[:bucket]; output
    must equal the full-cache read (reference: autobucketing.py:226)."""
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                                 build_mesh)
    prompts = np.random.default_rng(9).integers(
        1, 500, size=(2, 12)).astype(np.int32)

    def run(bucketing):
        tcfg = TpuConfig(batch_size=2, seq_len=256, max_context_length=16,
                         dtype="float32", output_logits=True,
                         enable_bucketing=bucketing,
                         token_generation_buckets=[32, 64, 256] if bucketing
                         else None)
        icfg = LlamaInferenceConfig(
            tcfg, load_config=load_pretrained_config(hd64_ckpt))
        app = CausalLMApplication(hd64_ckpt, icfg, LlamaFamily,
                                  mesh=build_mesh(MeshConfig(tp=1)))
        app.load_weights()
        app.init_cache()
        return app.generate(prompts, max_new_tokens=30, return_logits=True), app

    out_b, app_b = run(True)
    out_f, _ = run(False)
    bucketed_keys = [
        k for k in app_b._compiled
        if (k[0] == "decode_loop" and isinstance(k[1], tuple) and k[1][1])
        or (k[0] == "token_generation_model" and k[1])]
    assert bucketed_keys, f"no bucketed decode graphs: {list(app_b._compiled)}"
    np.testing.assert_array_equal(out_b["generated"], out_f["generated"])
    for a, b in zip(out_b["logits"], out_f["logits"]):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)


def test_decode_attention_bf16_io(rng):
    """bf16 in/out (the bench dtype): fp32 softmax inside, bf16 result."""
    b, s, hq, hkv, d = 2, 128, 8, 2, 64
    lens = np.array([64, 100], np.int32)
    mk = lambda *sh: _rand(rng, *sh).astype(jnp.bfloat16)
    q, kc, vc = mk(b, hq, d), mk(b, hkv, d, s), mk(b, hkv, s, d)
    nk, nv = mk(b, hkv, d), mk(b, hkv, d)
    got = _run_kernel(q, kc, vc, nk, nv, lens, d ** -0.5)
    want = _reference(q.astype(jnp.float32), kc.astype(jnp.float32),
                      vc.astype(jnp.float32), nk.astype(jnp.float32),
                      nv.astype(jnp.float32), lens, d ** -0.5)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# Ragged paged decode kernel (ops/decode_attention.paged_decode_attention)
# ---------------------------------------------------------------------------

def _paged_reference(q, k_pages, v_pages, nk, nv, lens, table, scale,
                     window=0, soft_cap=None, sink=None):
    """XLA gather-path reference (what model_base paged_forward_step does on
    the non-kernel branch): gather the whole block table, write the active
    token at each row's position, mha with the decode mask."""
    from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
    li = 0
    k_all = np.array(bkv.gather_block_kv(k_pages[li], jnp.asarray(table)))
    v_all = np.array(bkv.gather_block_kv(v_pages[li], jnp.asarray(table)))
    b = q.shape[0]
    rows = np.arange(b)
    k_all[rows, np.asarray(lens)] = np.asarray(nk)
    v_all[rows, np.asarray(lens)] = np.asarray(nv)
    positions = jnp.asarray(lens)[:, None]
    mask = attn_ops.decode_mask(positions, k_all.shape[1], window=window)
    out = attn_ops.mha(q[:, None], jnp.asarray(k_all), jnp.asarray(v_all),
                       mask, scale, logits_soft_cap=soft_cap, sink=sink)
    return out[:, 0]


def _paged_setup(rng, b, hq, hkv, d, bs, mb, lens, num_blocks=None):
    """Random pages + a block table assigning distinct physical pages in a
    scrambled order (block 0 = null)."""
    n = num_blocks or (1 + b * mb)
    k_pages = _rand(rng, 1, n, bs, hkv, d)
    v_pages = _rand(rng, 1, n, bs, hkv, d)
    perm = rng.permutation(n - 1)[:b * mb] + 1
    table = np.zeros((b, mb), np.int32)
    for i in range(b):
        live = -(-int(lens[i] + 1) // bs)
        table[i, :live] = perm[i * mb:i * mb + live]
    q = _rand(rng, b, hq, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    return q, k_pages, v_pages, nk, nv, table


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_paged_decode_kernel_matches_gather_path(rng, hq, hkv):
    b, d, bs, mb = 3, 64, 32, 8
    lens = np.array([5, 100, 255], np.int32)
    q, kp, vp, nk, nv, table = _paged_setup(rng, b, hq, hkv, d, bs, mb, lens)
    scale = d ** -0.5
    got = da.paged_decode_attention(
        q, kp, vp, nk, nv, jnp.asarray(0, jnp.int32),
        jnp.asarray(lens, jnp.int32), jnp.asarray(table), scale=scale,
        interpret=True)
    want = _paged_reference(q, kp, vp, nk, nv, lens, table, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hq,hkv,d,lens,soft_cap", [
    (4, 2, 64, (200, 90), None),
    # ISSUE 57: 8 kv rows under 16 query heads each, scored kv row by kv
    # row: a sink a head and a soft cap, the window inside the second block
    (128, 8, 128, (400, 90), 30.0)],
    ids=["two-heads-of-64", "command-a-plus-soft-cap"])
def test_paged_decode_kernel_window_and_sink(rng, hq, hkv, d, lens, soft_cap):
    b, bs, mb = 2, 32, 16
    lens = np.array(lens, np.int32)
    q, kp, vp, nk, nv, table = _paged_setup(rng, b, hq, hkv, d, bs, mb, lens)
    scale = d ** -0.5
    sink = _rand(rng, hq)
    got = da.paged_decode_attention(
        q, kp, vp, nk, nv, jnp.asarray(0, jnp.int32),
        jnp.asarray(lens, jnp.int32), jnp.asarray(table), scale=scale,
        window=jnp.asarray(64, jnp.int32), sink=sink, soft_cap=soft_cap,
        interpret=True)
    want = _paged_reference(q, kp, vp, nk, nv, lens, table, scale,
                            window=64, sink=sink, soft_cap=soft_cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_kernel_zero_len_row(rng):
    b, hq, hkv, d, bs, mb = 2, 4, 2, 64, 32, 4
    lens = np.array([0, 60], np.int32)
    q, kp, vp, nk, nv, table = _paged_setup(rng, b, hq, hkv, d, bs, mb, lens)
    scale = d ** -0.5
    got = da.paged_decode_attention(
        q, kp, vp, nk, nv, jnp.asarray(0, jnp.int32),
        jnp.asarray(lens, jnp.int32), jnp.asarray(table), scale=scale,
        interpret=True)
    want = _paged_reference(q, kp, vp, nk, nv, lens, table, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_kernel_stacked_layers(rng):
    """Layer addressing through scalar prefetch on the stacked page cache."""
    L, b, hq, hkv, d, bs, mb = 3, 2, 4, 2, 64, 32, 4
    lens = np.array([40, 100], np.int32)
    n = 1 + b * mb
    kp = _rand(rng, L, n, bs, hkv, d)
    vp = _rand(rng, L, n, bs, hkv, d)
    perm = rng.permutation(n - 1)[:b * mb] + 1
    table = np.zeros((b, mb), np.int32)
    for i in range(b):
        live = -(-int(lens[i] + 1) // bs)
        table[i, :live] = perm[i * mb:i * mb + live]
    q = _rand(rng, b, hq, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    scale = d ** -0.5
    for li in range(L):
        got = da.paged_decode_attention(
            q, kp, vp, nk, nv, jnp.asarray(li, jnp.int32),
            jnp.asarray(lens, jnp.int32), jnp.asarray(table), scale=scale,
            interpret=True)
        want = _paged_reference(q, kp[li:li + 1], vp[li:li + 1], nk, nv,
                                lens, table, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"layer {li}")


# ISSUE 33: the kernel's walk follows a row's LIVE pages, several to a compute
# block. Both benchmark cells' attention geometries (query heads, kv heads,
# head_dim), block 32; every case a row beside a second, ordinary row.
# olmo-hybrid (ISSUE 34): 30 heads in a pool of 32 head slots
# (block_kv_cache.pool_kv_heads), one page a compute block.
# qwen3-next (ISSUE 36): 2 kv heads of 256 lanes, 8 query heads each: both
# heads of a token share ONE row of 512 lanes (paged_pool_fold: few heads
# of whole vregs), sixteen pages a compute block.
_CELLS = {"olmoe": (16, 16, 128), "granite": (32, 8, 64),
          "olmo-hybrid": (32, 32, 128), "qwen3-next": (16, 2, 256),
          "command-a-plus": (128, 8, 128)}
_BS = 32


def _cell_plan(cell, dtype, mb):
    hq, hkv, d = _CELLS[cell]
    return da.paged_block_plan(_BS, hkv, hq // hkv, d, dtype, mb)


def _walk_case(case, pages, mb):
    """(prior lengths of the rows, window) of a named case; ``pages`` is the
    call's pages a compute block, ``mb`` the table's width."""
    block = pages * _BS
    lens = {"len0": 0, "len1": 1, "len31": 31, "len32": 32, "len33": 33,
            "block_less_one": block - 1, "block": block,
            "block_plus_one": block + 1, "full_table": mb * _BS - 1,
            # the window's first token lies inside the second compute block
            # (at 1.5 blocks less 20)
            "window_in_block": 2 * block - 20,
            "dead_entries_nan": block + 70, "bf16": block + 70}[case]
    window = block // 2 if case == "window_in_block" else 0
    return np.array([lens, 75], np.int32), window


@pytest.mark.parametrize("case", [
    "len0", "len1", "len31", "len32", "len33", "block_less_one", "block",
    "block_plus_one", "full_table", "window_in_block", "dead_entries_nan",
    "bf16"])
@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_paged_decode_walks_live_pages(rng, cell, case):
    hq, hkv, d = _CELLS[cell]
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    # ... and wide enough for the longest case (a block + 70 tokens)
    mb = max(2 * _cell_plan(cell, dtype, 64).pages + 1, 5)
    plan = _cell_plan(cell, dtype, mb)
    assert plan.fold == {"granite": 2, "qwen3-next": 2}.get(cell, 1)
    assert plan.pages == 1 if cell == "olmo-hybrid" else plan.pages >= 2
    assert plan.d == {"qwen3-next": 512}.get(cell, 128)
    # ISSUE 57: 8 kv rows a token under 16 query heads each are scored kv
    # row by kv row, eight pages or more a block; every other cell keeps the
    # block-diagonal form
    rows_form = cell == "command-a-plus"
    assert plan.form == ("mxu-kv-rows" if rows_form else "mxu-blockdiag")
    assert plan.pages >= 8 or not rows_form
    lens, window = _walk_case(case, plan.pages, mb)
    b, scale = len(lens), d ** -0.5
    # physical pages shuffled and non-contiguous (_paged_setup), block 0 null
    q, kp, vp, nk, nv, table = (
        x.astype(dtype) if isinstance(x, jnp.ndarray) else x
        for x in _paged_setup(rng, b, hq, hkv, d, _BS, mb, lens,
                              num_blocks=1 + 2 * b * mb))

    def run(kp_, vp_, table_):
        return da.paged_decode_attention(
            q, kp_, vp_, nk, nv, jnp.asarray(0, jnp.int32),
            jnp.asarray(lens, jnp.int32), jnp.asarray(table_), scale=scale,
            window=jnp.asarray(window, jnp.int32), interpret=True)

    got = run(kp, vp, table)
    if case == "dead_entries_nan":
        # a table 4 x wider whose every dead entry (past the row's last live
        # page) names a page of NaN: the result is the narrow table's, bit
        # for bit - only live pages are read
        wide = np.zeros((b, 4 * mb), np.int32)
        wide[:, :mb] = table
        spare = np.setdiff1d(np.arange(1, kp.shape[1]), table.ravel())
        for i in range(b):
            live = -(-int(lens[i] + 1) // _BS)
            wide[i, live:] = rng.choice(spare, 4 * mb - live)
        poison = np.zeros(kp.shape[1], bool)
        poison[spare] = True
        poison[0] = True                     # the null page too
        kp_nan, vp_nan = (jnp.where(poison[None, :, None, None, None],
                                    jnp.nan, x) for x in (kp, vp))
        np.testing.assert_array_equal(np.asarray(run(kp_nan, vp_nan, wide)),
                                      np.asarray(got))
    f32 = [np.asarray(x, np.float32) for x in (q, kp, vp, nk, nv)]
    want = _paged_reference(*(jnp.asarray(x) for x in f32), lens, table,
                            scale, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


# ISSUE 57: what the plan gives each benchmark cell's attention geometry
# (query heads, a shard's kv heads, head_dim, the table's width), letter for
# letter as its ``precompile widths`` line prints it: only 8 kv rows a token
# under 16 query heads each take the kv-row form.
@pytest.mark.parametrize("cell,hq,hkv,d,mb,plan,note", [
    ("olmoe-chat-steady", 16, 16, 128, 64, (4, 1, 16, 1, 128),
     "pages=4 heads=16 form=mxu-blockdiag fold=1"),
    ("olmoe-longprompt-closed", 16, 16, 128, 128, (4, 1, 16, 1, 128),
     "pages=4 heads=16 form=mxu-blockdiag fold=1"),
    ("granite-h-chat-closed", 32, 8, 64, 64, (8, 2, 4, 8, 128),
     "pages=8 heads=8 form=mxu-blockdiag fold=2 stored"),
    ("olmo-hybrid-reason-closed", 32, 32, 128, 512, (1, 1, 32, 1, 128),
     "pages=1 heads=32 form=mxu-blockdiag fold=1"),
    ("qwen3-next-rag-closed", 16, 2, 256, 256, (16, 2, 1, 16, 512),
     "pages=16 heads=2 form=mxu-blockdiag fold=2 stored"),
    ("smallthinker-mixedlen-closed", 28, 4, 128, 480, (16, 4, 1, 28, 512),
     "pages=16 heads=4 form=mxu-blockdiag fold=4 stored"),
    ("keye-vl2-videoqa-closed", 32, 4, 128, 384, (16, 4, 1, 32, 512),
     "pages=16 heads=4 form=mxu-blockdiag fold=4 stored"),
    ("phi4-flash-reason-closed", 40, 10, 128, 512, (12, 10, 1, 40, 1280),
     "pages=12 heads=10 form=mxu-blockdiag fold=10 stored"),
    ("command-a-plus-agent-closed", 128, 8, 128, 384, (8, 1, 8, 16, 128),
     "pages=8 heads=8 form=mxu-kv-rows fold=1"),
    # no cell's: 8 kv heads of 256 lanes under 8 query heads each keep the
    # block-diagonal form (Mosaic refuses the 32-bit view of a 256-lane slot)
    ("heads-of-256-lanes", 64, 8, 256, 384, (2, 1, 8, 8, 256),
     "pages=2 heads=8 form=mxu-blockdiag fold=1")])
def test_the_plan_of_every_cells_geometry(cell, hq, hkv, d, mb, plan, note):
    got = da.paged_block_plan(_BS, hkv, hq // hkv, d, jnp.bfloat16, mb)
    assert tuple(got[:5]) == plan
    assert got.note(stored=True) == note + " prefetch=across-rows"
    assert (got.form == da.PAGED_ROWS_FORM) == (
        got.fold == 1 and got.d == 128 and got.hkv % da.PAGED_ROW_TILE == 0
        and got.g % da.PAGED_ROW_TILE == 0)


def test_paged_decode_kv_rows_take_a_selection(rng):
    """ISSUE 57: the kv-row form takes a learned sparse selection as the
    block-diagonal form does - a column attended where live AND selected,
    the active token where its own flag is set."""
    hq, hkv, d, mb = 128, 8, 128, 20
    lens = np.array([333, 75], np.int32)
    b, scale = len(lens), d ** -0.5
    assert da.paged_block_plan(_BS, hkv, hq // hkv, d, jnp.float32,
                               mb).form == da.PAGED_ROWS_FORM
    q, kp, vp, nk, nv, table = _paged_setup(rng, b, hq, hkv, d, _BS, mb, lens)
    select = rng.random((b, mb * _BS)) < 0.3
    select[0, lens[0]] = True          # row 0 attends its own token, row 1
    select[1, lens[1]] = False         # does not
    select[1, 3] = True
    got = da.paged_decode_attention(
        q, kp, vp, nk, nv, jnp.asarray(0, jnp.int32), jnp.asarray(lens),
        jnp.asarray(table), scale=scale, select=jnp.asarray(select),
        interpret=True)
    from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
    k_all = np.array(bkv.gather_block_kv(kp[0], jnp.asarray(table)))
    v_all = np.array(bkv.gather_block_kv(vp[0], jnp.asarray(table)))
    k_all[np.arange(b), lens], v_all[np.arange(b), lens] = nk, nv
    mask = np.arange(mb * _BS)[None] <= lens[:, None]
    mask = jnp.asarray(mask & select)[:, None, :]
    want = attn_ops.mha(q[:, None], jnp.asarray(k_all), jnp.asarray(v_all),
                        mask, scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Quantized-KV admission (reference: fp8 KV cache feeding the TKG kernel,
# kv_cache_manager.py:636-692): the kernel dequantizes on the block load.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype,kv_scale", [
    (jnp.float8_e4m3fn, None),        # direct-cast fp8
    (jnp.float8_e4m3fn, 0.25),        # scaled fp8
    (jnp.bfloat16, 2.0),              # scaled bf16
])
def test_decode_attention_quantized_kv(rng, kv_dtype, kv_scale):
    from neuronx_distributed_inference_tpu.modules import kv_cache as kv
    b, s, hq, hkv, d = 2, 256, 4, 2, 64
    lens = np.array([100, 255], np.int32)
    q = _rand(rng, b, hq, d)
    kc_f = _rand(rng, b, hkv, d, s)
    vc_f = _rand(rng, b, hkv, s, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    # quantize the cache the way the write path does
    kc_q = kv.quantize_kv(kc_f, kv_dtype, kv_scale)
    vc_q = kv.quantize_kv(vc_f, kv_dtype, kv_scale)
    scale = d ** -0.5
    got = da.decode_attention(
        q, kc_q, vc_q, nk, nv, jnp.asarray(lens, jnp.int32), scale=scale,
        kv_scale=kv_scale, block_s=64, interpret=True)
    # XLA-path reference over the DEQUANTIZED cache with a full-precision
    # active token (the kernel folds the active token in-registers)
    kc_d = kv.dequantize_kv(kc_q, jnp.float32, kv_scale)
    vc_d = kv.dequantize_kv(vc_q, jnp.float32, kv_scale)
    want = _reference(q, kc_d, vc_d, nk, nv, lens, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hq,hkv,d,bs,kv_dtype,kv_scale", [
    (4, 2, 64, 64, jnp.float8_e4m3fn, 0.5),
    # ISSUE 57: 8 kv rows under 16 query heads each; 300 tokens end in the
    # kv-row form's second block
    (128, 8, 128, 32, jnp.float8_e4m3fn, None),
    (128, 8, 128, 32, jnp.float8_e4m3fn, 0.25),
    (128, 8, 128, 32, jnp.bfloat16, 2.0)],
    ids=["two-heads-of-64", "command-a-plus-fp8", "command-a-plus-fp8-scaled",
         "command-a-plus-bf16-scaled"])
def test_paged_decode_attention_quantized_kv(rng, hq, hkv, d, bs, kv_dtype,
                                             kv_scale):
    from neuronx_distributed_inference_tpu.modules import kv_cache as kv
    b = 2
    if bs == 64:
        nblocks, mb = 8, 4
        lens = np.array([70, 130], np.int32)
        table = jnp.asarray(np.array([[1, 2, 0, 0], [3, 4, 5, 0]], np.int32))
    else:
        nblocks, mb = 16, 12
        lens = np.array([70, 300], np.int32)
        table = np.zeros((b, mb), np.int32)
        table[0, :3], table[1, :10] = [1, 2, 3], np.arange(4, 14)
        table = jnp.asarray(table)
        # a kv row is read out of a bf16 or 32-bit slot; an 8-bit pool
        # keeps the block-diagonal form at this geometry too
        assert da.paged_block_plan(bs, hkv, hq // hkv, d, kv_dtype, mb).form \
            == ("mxu-kv-rows" if kv_dtype == jnp.bfloat16
                else "mxu-blockdiag")
    q = _rand(rng, b, hq, d)
    kp_f = _rand(rng, 1, nblocks, bs, hkv, d)
    vp_f = _rand(rng, 1, nblocks, bs, hkv, d)
    nk = _rand(rng, b, hkv, d)
    nv = _rand(rng, b, hkv, d)
    kp_q = kv.quantize_kv(kp_f, kv_dtype, kv_scale)
    vp_q = kv.quantize_kv(vp_f, kv_dtype, kv_scale)
    scale = d ** -0.5
    got = da.paged_decode_attention(
        q, kp_q, vp_q, nk, nv, jnp.zeros((), jnp.int32),
        jnp.asarray(lens), table, scale=scale, kv_scale=kv_scale,
        interpret=True)
    # gather-path reference: dequantized pages -> contiguous rows
    kp_d = np.asarray(kv.dequantize_kv(kp_q, jnp.float32, kv_scale))[0]
    vp_d = np.asarray(kv.dequantize_kv(vp_q, jnp.float32, kv_scale))[0]
    tbl = np.asarray(table)
    k_rows = kp_d[tbl].reshape(b, mb * bs, hkv, d)
    v_rows = vp_d[tbl].reshape(b, mb * bs, hkv, d)
    rows = np.arange(b)
    k_rows[rows, lens] = np.asarray(nk)
    v_rows[rows, lens] = np.asarray(nv)
    mask = attn_ops.decode_mask(jnp.asarray(lens)[:, None], mb * bs)
    want = attn_ops.mha(q[:, None], jnp.asarray(k_rows),
                        jnp.asarray(v_rows), mask, scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_kernel_e2e_fp8_kv(hd64_ckpt):
    """fp8-KV serving must ADMIT the kernel (no more full-gather fallback)
    and reproduce the XLA path's tokens/logits over the same fp8 cache."""
    from neuronx_distributed_inference_tpu.config import (
        TpuConfig, load_pretrained_config)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.parallel.mesh import (
        MeshConfig, build_mesh)

    def fp8_app(enabled):
        tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                         output_logits=True, enable_bucketing=False,
                         kv_cache_dtype="float8_e4m3fn",
                         kv_cache_quant=True, kv_cache_scale=2.0,
                         attn_block_tkg_kernel_enabled=enabled)
        icfg = LlamaInferenceConfig(tcfg,
                                    load_config=load_pretrained_config(
                                        hd64_ckpt))
        app = CausalLMApplication(hd64_ckpt, icfg, LlamaFamily,
                                  mesh=build_mesh(MeshConfig(tp=1)))
        app.load_weights().init_cache()
        return app

    prompts = np.random.default_rng(7).integers(
        1, 500, size=(2, 12)).astype(np.int32)
    app_k = fp8_app(True)
    assert app_k.spec.kv_scale == 2.0
    assert app_k.cache["k"].dtype == jnp.float8_e4m3fn
    out_k = app_k.generate(prompts, max_new_tokens=8, return_logits=True)
    out_x = fp8_app(False).generate(prompts, max_new_tokens=8,
                                    return_logits=True)
    # the kernel folds the ACTIVE token full-precision while the XLA path
    # reads it back quantized — tolerance covers that one-token delta
    for a, b in zip(out_k["logits"], out_x["logits"]):
        np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)


# ---------------------------------------------------------------------------
# scripts/paged_decode_time.py: the clock behind the plan's two forms. A time
# comes from a chip only; here its shapes and its yardstick are held.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_decode_time():
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "paged_decode_time", Path(__file__).resolve().parent.parent
        / "scripts" / "paged_decode_time.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", ["command-a-plus", "olmoe", "granite",
                                  "olmo-hybrid"])
def test_the_scripts_cells_and_bytes_are_the_benchmarks(paged_decode_time,
                                                        cell):
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmark"))
    from harness.kernel_bytes import paged_decode_min_bytes
    hq, hkv, d = paged_decode_time.CELLS[cell]
    assert (hq, hkv, d) == _CELLS[cell]
    cfg = dict(num_attention_heads=hq, num_key_value_heads=hkv, head_dim=d,
               tp=1, dtype="bfloat16")
    for tokens, window, seen in [(2048, 0, 2048), (6144, 4096, 4096),
                                 (2048, 4096, 2048)]:
        assert paged_decode_time.min_bytes(cell, tokens, window) == \
            paged_decode_min_bytes(cfg, 32 * seen, 32)
    lens = paged_decode_time.row_lengths(6144)
    assert len(lens) == 32 and lens.min() == 4608 and lens.max() == 7680


def test_the_script_prints_no_time_without_a_chip(paged_decode_time, capsys):
    assert paged_decode_time.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err
