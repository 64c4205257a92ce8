"""Paged/block KV cache tests (reference analog:
test/unit/modules/kvcache block manager tests + prefix caching)."""

import numpy as np
import pytest

from neuronx_distributed_inference_tpu.config import InferenceConfig, TpuConfig
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.llama import (LlamaFamily,
                                                            LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
    BlockAllocator, BlockKVSpec, gather_block_kv, gather_layer_kv,
    slots_from_table, write_slots)

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_allocator_basic_and_free():
    a = BlockAllocator(num_blocks=8, block_size=4, enable_prefix_caching=False)
    blocks, cached = a.allocate(list(range(10)))   # 3 blocks
    assert len(blocks) == 3 and cached == 0
    assert a.num_free == 4
    a.free(blocks)
    assert a.num_free == 7
    with pytest.raises(RuntimeError):
        a.free(blocks[:1])


def test_allocator_prefix_reuse():
    a = BlockAllocator(num_blocks=16, block_size=4)
    p = list(range(100, 112))                      # 3 full blocks
    b1, c1 = a.allocate(p)
    assert c1 == 0
    b2, c2 = a.allocate(p + [7, 8])                # same prefix + extra
    assert c2 == 12                                # all 3 full blocks reused
    assert b2[:3] == b1[:3]
    # divergent prefix shares only the common full blocks
    q = p[:8] + [999, 998, 997, 996]
    b3, c3 = a.allocate(q)
    assert c3 == 8 and b3[:2] == b1[:2] and b3[2] != b1[2]


def test_allocator_cached_block_eviction():
    a = BlockAllocator(num_blocks=5, block_size=2)   # 4 usable
    b1, _ = a.allocate([1, 2, 3, 4])                 # 2 full blocks cached
    a.free(b1)                                       # refs 0, stay resident
    assert a.num_free == 4
    b2, c2 = a.allocate([1, 2, 3, 4])                # comes back from cache
    assert c2 == 4 and b2 == b1
    a.free(b2)
    # exhaust: need 4 fresh blocks for different content -> evicts cached
    b3, c3 = a.allocate([9, 9, 9, 9, 9, 9, 9, 9])
    assert c3 == 0 and len(b3) == 4


# ---------------------------------------------------------------------------
# device ops
# ---------------------------------------------------------------------------

def test_write_and_gather_roundtrip():
    spec = BlockKVSpec(num_layers=1, num_blocks=5, block_size=4,
                       num_kv_heads=2, head_dim=4, dtype=jnp.float32)
    layer = jnp.zeros(spec.shape[1:], jnp.float32)
    rng = np.random.default_rng(0)
    new = rng.normal(size=(2, 6, 2, 4)).astype(np.float32)   # 2 seqs, 6 toks
    bt = np.array([[1, 2], [3, 4]], np.int32)
    pos = np.broadcast_to(np.arange(6, dtype=np.int64), (2, 6)).copy()
    slots = slots_from_table(bt, pos, 4)
    out = write_slots(layer, jnp.asarray(new), jnp.asarray(slots))
    view = gather_block_kv(out, jnp.asarray(bt))             # (2, 8, 2, 4)
    np.testing.assert_allclose(np.asarray(view[:, :6]), new, rtol=1e-6)
    assert np.all(np.asarray(view[:, 6:]) == 0)


def test_negative_slots_dropped():
    layer = jnp.ones((3, 2, 1, 2), jnp.float32)
    new = jnp.full((1, 2, 1, 2), 7.0)
    slots = jnp.array([[-1, 3]], jnp.int32)
    out = np.asarray(write_slots(layer, new, slots)).reshape(6, 2)
    assert out[3, 0] == 7.0
    # slot -1 must NOT wrap to the last flat slot (regression: jax scatter
    # wraps negatives; a padded write once clobbered another row's block)
    untouched = [i for i in range(6) if i != 3]
    assert (out[untouched] == 1.0).all()


@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("slots", [2, 8], ids=["page-as-rows", "page"])
def test_flat_gather_equals_the_layer_slice(layer, traced, slots):
    """One gather from the flat (L*N, ...) pool reads what cutting the
    layer out and gathering from it reads, bit for bit — null block
    (table entry 0) and a repeated block included (ISSUE 31); a page of
    fewer slots than a tile has rows goes through it as the ``(tokens x
    slots, lanes)`` matrix it is in memory (ISSUE 41)."""
    rng = np.random.default_rng(31)
    L, n = 5, 7
    pool = jnp.asarray(
        rng.normal(size=(L, n, 4, slots, 8)).astype(np.float32))
    bt = jnp.asarray([[3, 1, 0, 0], [6, 6, 2, 0], [0, 0, 0, 0]], jnp.int32)
    want = gather_block_kv(
        jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False), bt)
    if traced:
        got = jax.jit(gather_layer_kv)(pool, jnp.int32(layer), bt)
    else:
        got = gather_layer_kv(pool, layer, bt)
    assert got.shape == (3, 16, slots, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the null entries read THIS layer's block 0, not layer 0's
    for half in np.asarray(got[0, 8:]).reshape(2, 4, slots, 8):
        np.testing.assert_array_equal(half, np.asarray(pool[layer, 0]))


# ---------------------------------------------------------------------------
# end-to-end: paged generate == contiguous generate
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cfg_pair():
    hf = dict(model_type="llama", hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, vocab_size=512,
              rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
              tie_word_embeddings=False, torch_dtype="float32")
    base = dict(batch_size=2, seq_len=64, dtype="float32",
                enable_bucketing=False)
    contig = LlamaInferenceConfig(TpuConfig(**base), **hf)
    paged = LlamaInferenceConfig(
        TpuConfig(**base, is_block_kv_layout=True, pa_block_size=8,
                  is_prefix_caching=True), **hf)
    return contig, paged


def test_paged_matches_contiguous(cfg_pair):
    contig_cfg, paged_cfg = cfg_pair
    app_c = CausalLMApplication(None, contig_cfg, LlamaFamily)
    app_c.init_random_weights(7).init_cache()
    app_p = PagedCausalLMApplication(None, paged_cfg, LlamaFamily)
    app_p.init_random_weights(7).init_cache()

    ids = np.random.default_rng(0).integers(1, 512, size=(2, 11), dtype=np.int64)
    mask = np.ones_like(ids); mask[0, 9:] = 0; ids[0, 9:] = 0
    want = app_c.generate(ids, attention_mask=mask, max_new_tokens=8)
    got = app_p.generate(ids, attention_mask=mask, max_new_tokens=8)
    np.testing.assert_array_equal(got["generated"], want["generated"])
    assert got["cached_tokens"].sum() == 0

    # --- prefix caching: same prompts again reuse full blocks and match ---
    app_p.release()
    got2 = app_p.generate(ids, attention_mask=mask, max_new_tokens=8)
    assert got2["cached_tokens"][0] == 8     # 9-token row: one full block
    assert got2["cached_tokens"][1] == 8     # 11-token row: one full block
    np.testing.assert_array_equal(got2["generated"], want["generated"])
    app_p.release()


def test_chunked_prefill_matches(cfg_pair):
    """Chunked prefill (fixed windows over the prompt, growing paged KV) must
    be token-identical to one-shot prefill."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    contig_cfg, _ = cfg_pair
    hf = {k: getattr(contig_cfg, k) for k in
          ("model_type", "hidden_size", "intermediate_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "vocab_size", "rms_norm_eps", "rope_theta", "hidden_act",
           "tie_word_embeddings")}
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=False, is_block_kv_layout=True,
                     pa_block_size=8, is_chunked_prefill=True,
                     chunked_prefill_config=ChunkedPrefillConfig(
                         kernel_q_tile_size=8))
    chunked_cfg = LlamaInferenceConfig(tcfg, **hf)
    app_c = CausalLMApplication(None, contig_cfg, LlamaFamily)
    app_c.init_random_weights(7).init_cache()
    app_k = PagedCausalLMApplication(None, chunked_cfg, LlamaFamily)
    app_k.init_random_weights(7).init_cache()
    ids = np.random.default_rng(2).integers(1, 512, size=(2, 21), dtype=np.int64)
    mask = np.ones_like(ids); mask[0, 17:] = 0; ids[0, 17:] = 0
    want = app_c.generate(ids, attention_mask=mask, max_new_tokens=6)
    got = app_k.generate(ids, attention_mask=mask, max_new_tokens=6)
    np.testing.assert_array_equal(got["generated"], want["generated"])
    app_k.release()


def test_chunked_intra_batch_prefix_sharing(cfg_pair):
    """Regression: two IDENTICAL prompts in one chunked-prefill batch. Row 1's
    prefix-cache hit on row 0's just-allocated blocks must not read slots row
    0 hasn't written yet (later chunks)."""
    from neuronx_distributed_inference_tpu.config import ChunkedPrefillConfig
    contig_cfg, _ = cfg_pair
    hf = {k: getattr(contig_cfg, k) for k in
          ("model_type", "hidden_size", "intermediate_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "vocab_size", "rms_norm_eps", "rope_theta", "hidden_act",
           "tie_word_embeddings")}
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=False, is_block_kv_layout=True,
                     pa_block_size=8, is_prefix_caching=True,
                     is_chunked_prefill=True,
                     chunked_prefill_config=ChunkedPrefillConfig(
                         kernel_q_tile_size=8))
    app_k = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                     LlamaFamily)
    app_k.init_random_weights(7).init_cache()
    app_c = CausalLMApplication(None, contig_cfg, LlamaFamily)
    app_c.init_random_weights(7).init_cache()
    row = np.random.default_rng(3).integers(1, 512, size=(16,), dtype=np.int64)
    ids = np.stack([row, row])                    # identical prompts
    want = app_c.generate(ids, max_new_tokens=4)
    got = app_k.generate(ids, max_new_tokens=4)
    np.testing.assert_array_equal(got["generated"], want["generated"])
    app_k.release()


def test_paged_chunked_decode_matches_single_step(cfg_pair):
    """Fetch-free paged decode (model_base.paged_decode_loop): chunked
    decode with IN-GRAPH slot mapping must equal the per-step path
    (reference: in-graph tokengen slot mapping,
    block_kv_cache_manager.py:376-430)."""
    _, paged_cfg = cfg_pair
    ids = np.random.default_rng(3).integers(1, 512, size=(2, 9),
                                            dtype=np.int64)
    app1 = PagedCausalLMApplication(None, paged_cfg, LlamaFamily)
    app1.init_random_weights(7).init_cache()
    ref = app1.generate(ids, max_new_tokens=9)

    import copy
    cfg4 = copy.deepcopy(paged_cfg)
    cfg4.tpu_config.decode_chunk_tokens = 4
    app4 = PagedCausalLMApplication(None, cfg4, LlamaFamily)
    app4.init_random_weights(7).init_cache()
    got = app4.generate(ids, max_new_tokens=9)
    np.testing.assert_array_equal(got["sequences"], ref["sequences"])
    assert ("paged_loop", 4) in app4._compiled


def test_paged_ragged_kernel_e2e_matches_contiguous():
    """head_dim=64 admits the ragged paged decode kernel
    (ops/decode_attention.paged_decode_attention, default-on for paged
    decode) — paged generate must still match the contiguous app."""
    hf = dict(model_type="llama", hidden_size=256, intermediate_size=512,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=64, vocab_size=512,
              rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
              tie_word_embeddings=False, torch_dtype="float32")
    base = dict(batch_size=2, seq_len=64, dtype="float32",
                enable_bucketing=False)
    app_c = CausalLMApplication(None, LlamaInferenceConfig(
        TpuConfig(**base), **hf), LlamaFamily)
    app_c.init_random_weights(3).init_cache()
    app_p = PagedCausalLMApplication(None, LlamaInferenceConfig(
        TpuConfig(**base, is_block_kv_layout=True, pa_block_size=8), **hf),
        LlamaFamily)
    app_p.init_random_weights(3).init_cache()
    assert app_p.spec.head_dim == 64 and app_p.spec.decode_kernel is None

    ids = np.random.default_rng(1).integers(1, 512, size=(2, 13),
                                            dtype=np.int64)
    mask = np.ones_like(ids); mask[1, 10:] = 0; ids[1, 10:] = 0
    want = app_c.generate(ids, attention_mask=mask, max_new_tokens=10)
    got = app_p.generate(ids, attention_mask=mask, max_new_tokens=10)
    np.testing.assert_array_equal(got["generated"], want["generated"])
    app_p.release()


# ---------------------------------------------------------------------------
# the page the pool is allocated by (ISSUE 41)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads, lanes, tp, page", [
    (8, 64, 1, (4, 128)),        # granite-4.0-h, Llama-3.2-1B
    (2, 64, 1, (1, 128)),
    (32, 64, 1, (16, 128)),      # two tiles' worth of narrow heads
    (8, 32, 1, (2, 128)),
    (3, 64, 1, (3, 64)),         # an odd count does not fold evenly
    (8, 96, 1, (8, 96)),         # 96 lanes do not divide a vreg
    (16, 128, 1, (16, 128)),     # OLMoE: whole tiles of whole vregs
    (30, 128, 1, (32, 128)),     # Olmo-Hybrid: rounded up to whole tiles
    (2, 256, 1, (1, 512)),       # Qwen3-Next, as before
    (8, 64, 4, (4, 128)),        # a shard's 2 heads share its one slot
    (4, 64, 4, (4, 64)),         # a shard's one head has no neighbour
    (8, 128, 4, (4, 256)),       # a shard's 2 heads of whole vregs
    (32, 128, 4, (32, 128)),     # a shard's 8 heads: a slot a head
    (30, 128, 4, (30, 128)),     # heads that do not shard are not padded
])
def test_a_page_is_stored_as_the_decode_kernel_reads_a_shards(heads, lanes,
                                                              tp, page):
    """``pool_page`` is the one place that decides a page's shape: a
    shard's heads by ``paged_pool_fold``, the same bytes a token either
    way, and never a slot across two shards."""
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        pool_kv_heads, pool_page)
    slots, slot_lanes = pool_page(heads, lanes, tp)
    assert (slots, slot_lanes) == page
    assert slots * slot_lanes == pool_kv_heads(heads, tp) * lanes
    assert slots % tp == 0 or slot_lanes == lanes


HEADS_OF_64 = dict(
    model_type="llama", hidden_size=256, intermediate_size=512,
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
    head_dim=64, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
    hidden_act="silu", tie_word_embeddings=False, torch_dtype="float32")


@pytest.mark.parametrize("kv", [
    dict(kv_cache_dtype="bfloat16"),
    dict(kv_cache_dtype="float8_e4m3fn", kv_cache_quant=True,
         kv_cache_scale=2.0)], ids=["bf16", "fp8"])
def test_heads_of_64_two_to_a_slot_serve_the_same_tokens_and_bytes(
        kv, monkeypatch):
    """8 kv heads of 64 (granite-4.0-h's attention at a toy size) live four
    slots of 128 lanes to a token. Through ``PagedEngineAdapter`` - chunks
    of 8, decode steps on the kernel (interpreted), a preemption and its
    re-admission, a release, a prefix hit - the streams are those of a pool
    of a head a slot and (bf16) the contiguous cache's, and the pool, a
    token's ``slots x lanes`` as bytes, is that pool byte for byte: nothing
    that handles a page as bytes (allocator, tables, slot mapping, prefix
    hashing) can tell."""
    from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    rng = np.random.default_rng(41)
    a = rng.integers(1, 500, size=21).tolist()
    b = rng.integers(1, 500, size=13).tolist()
    c = a[:16] + rng.integers(1, 500, size=4).tolist()
    base = dict(seq_len=64, dtype="float32", **kv)

    def paged_app():
        tcfg = TpuConfig(batch_size=2, enable_bucketing=True,
                         context_encoding_buckets=[8, 16],
                         is_block_kv_layout=True, pa_block_size=8,
                         is_prefix_caching=True, **base)
        app = PagedCausalLMApplication(
            None, LlamaInferenceConfig(tcfg, **HEADS_OF_64), LlamaFamily)
        return app.init_random_weights(5).init_cache()

    def serve(app):
        ad = PagedEngineAdapter(app, prefill_chunk_tokens=8)
        first = ad.add_requests([0, 1], [a, b])
        out = {0: [first[0]], 1: [first[1]], 2: []}

        def decode(n):
            for _ in range(n):
                for sid, tok in ad.step().items():
                    out[sid].append(tok)
        decode(3)
        rec = ad.preempt(1)
        assert list(rec.tokens) == b + out[1]
        decode(1)                       # 0 runs on while 1 is out
        out[1].append(ad.add_requests([1], [list(rec.tokens)])[1])
        decode(2)
        ad.release([0])
        assert ad.prefix_warmth(c) == 16        # a's two full blocks
        out[2].append(ad.add_requests([2], [c])[2])
        decode(2)
        kernels = {k["site"]: k["reason"]
                   for k in app.warmup_state()["kernels"]
                   if k["site"] != "paged_prefill"}
        kernels["chunks"] = {
            (k["path"], k["reason"].split(": ")[-1].split(" pages=")[-1])
            for k in app.warmup_state()["kernels"]
            if k["site"] == "paged_prefill"}
        return out, kernels, {
            k: np.asarray(app.cache[k]).view(np.uint8) for k in "kv"}

    app = paged_app()
    assert app.cache["k"].shape == (2, app.cache["k"].shape[1], 8, 4, 128)
    got, kernels, pool = serve(app)
    # the chunks walk the stored page on the prefill kernel (two heads to a
    # 128-lane row, the queries placed in their own lanes); an fp8 pool
    # keeps the gather, and says so
    assert kernels == {
        "kv_pool": "page=4x128 heads=8x64",
        "paged_decode": "pages=8 heads=8 form=mxu-blockdiag fold=2 stored "
                        "prefetch=across-rows",
        "chunks": ({("xla", "pool stored as float8_e4m3fn")}
                   if "kv_cache_quant" in kv else
                   {("pallas-interpret",
                     "8 heads=8 fold=2 tile=8x8 window=0")})}
    # ... against a pool of a head a slot, as it was allocated before
    monkeypatch.setattr(bkv, "pool_page",
                        lambda heads, lanes, tp=1: (heads, lanes))
    plain = paged_app()
    assert plain.cache["k"].shape[3:] == (8, 64)
    want, kernels, plain_pool = serve(plain)
    assert kernels["paged_decode"].endswith(
        "fold=2 call prefetch=across-rows")
    assert {path for path, _ in kernels["chunks"]} == {"xla"}
    assert got == want
    for k in "kv":
        tokens = pool[k].shape[:3]
        ours, theirs = (x[k].reshape(tokens + (-1,))
                        for x in (pool, plain_pool))
        if "kv_cache_quant" in kv:
            np.testing.assert_array_equal(ours, theirs)
            continue
        # the first layer's K / V are the projections of the embeddings:
        # byte for byte. The second layer's lie behind the first's chunk
        # attention, which walked the stored page on the prefill kernel here
        # and gathered the plain pool there: the same values to a bf16
        # rounding of another summation order
        np.testing.assert_array_equal(ours[0], theirs[0])
        np.testing.assert_allclose(
            *(x[1].view(jnp.bfloat16).astype(np.float32)
              for x in (ours, theirs)), atol=2e-2)
    if "kv_cache_quant" in kv:
        # a chunk reads the chunks before it back out of the pool, 3 bits
        # of mantissa each, where the contiguous prefill is one dispatch:
        # the streams part ways for a reason that is not the page's
        return
    # ... and against the contiguous cache, a prompt at a time
    contig = CausalLMApplication(None, LlamaInferenceConfig(
        TpuConfig(batch_size=1, enable_bucketing=False, **base),
        **HEADS_OF_64), LlamaFamily)
    contig.init_random_weights(5).init_cache()
    for sid, prompt in ((0, a), (1, b), (2, c)):
        ref = contig.generate(np.asarray([prompt]),
                              max_new_tokens=len(got[sid]))
        assert got[sid] == ref["generated"][0].tolist(), sid
