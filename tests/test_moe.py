"""MoE tests: routing math, dense-vs-ragged expert path consistency, and
tiny-model goldens vs HF CPU for Mixtral and Qwen3-MoE (reference analog:
test/integration tiny_model/features MoE coverage, SURVEY §4)."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from neuronx_distributed_inference_tpu.config import (TpuConfig,
                                                      load_pretrained_config)
from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.family import get_family
from neuronx_distributed_inference_tpu.modules import moe as moe_mod
from neuronx_distributed_inference_tpu.ops import kernel_mode
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh,
                                                             mesh_from_config)


def _moe_spec(**over):
    kw = dict(num_experts=4, top_k=2, intermediate_size=32,
              normalize_topk=True, act="silu")
    kw.update(over)
    return moe_mod.MoESpec(**kw)


def test_route_topk_normalized(rng):
    spec = _moe_spec()
    h = jnp.asarray(rng.normal(size=(2, 3, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    top_vals, top_idx = moe_mod.route(spec, h, w)
    assert top_vals.shape == (2, 3, 2)
    assert top_idx.shape == (2, 3, 2)
    combine = moe_mod.combine_matrix(4, top_vals, top_idx)
    # exactly k nonzeros per token, summing to 1 (normalized)
    nz = (np.asarray(combine) > 0).sum(axis=-1)
    np.testing.assert_array_equal(nz, np.full((2, 3), 2))
    np.testing.assert_allclose(np.asarray(combine).sum(-1), 1.0, atol=1e-6)


def test_dense_vs_ragged_consistent(rng):
    """The two expert-compute paths must agree bitwise-closely."""
    spec = _moe_spec()
    b, t, h, i, e = 2, 5, 16, 32, 4
    x = jnp.asarray(rng.normal(size=(b, t, h)).astype(np.float32))
    wg = jnp.asarray(rng.normal(size=(e, h, i)).astype(np.float32) * 0.1)
    wu = jnp.asarray(rng.normal(size=(e, h, i)).astype(np.float32) * 0.1)
    wd = jnp.asarray(rng.normal(size=(e, i, h)).astype(np.float32) * 0.1)
    rw = jnp.asarray(rng.normal(size=(h, e)).astype(np.float32))
    top_vals, top_idx = moe_mod.route(spec, x, rw)
    dense = moe_mod.experts_dense(spec, x, top_vals, top_idx, wg, wu, wd)
    ragged = moe_mod.experts_ragged(spec, x, top_vals, top_idx, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ragged),
                               atol=1e-5, rtol=1e-5)


def test_moe_block_ep_sharded(rng):
    """moe_block under jit on a (ep=2, tp=2) mesh matches single-device."""
    spec = _moe_spec(dense_max_tokens=0)  # force ragged path
    b, t, h, i, e = 2, 4, 16, 32, 4
    x = rng.normal(size=(b, t, h)).astype(np.float32)
    w = {
        "router": rng.normal(size=(h, e)).astype(np.float32),
        "expert_gate": rng.normal(size=(e, h, i)).astype(np.float32) * 0.1,
        "expert_up": rng.normal(size=(e, h, i)).astype(np.float32) * 0.1,
        "expert_down": rng.normal(size=(e, i, h)).astype(np.float32) * 0.1,
    }
    ref = moe_mod.moe_block(spec, jnp.asarray(x),
                            {k: jnp.asarray(v) for k, v in w.items()})
    mesh = build_mesh(MeshConfig(tp=2, ep=2))
    with jax.sharding.set_mesh(mesh):
        out = jax.jit(lambda xx, ww: moe_mod.moe_block(spec, xx, ww))(
            jnp.asarray(x), {k: jnp.asarray(v) for k, v in w.items()})
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _save_tiny_moe(tmp_path, model_type):
    import transformers
    torch.manual_seed(0)
    if model_type == "mixtral":
        cfg = transformers.MixtralConfig(
            hidden_size=64, intermediate_size=96, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
            num_local_experts=4, num_experts_per_tok=2, rms_norm_eps=1e-5,
            max_position_embeddings=128, torch_dtype="float32",
            tie_word_embeddings=False, sliding_window=None)
        model = transformers.MixtralForCausalLM(cfg)
    else:
        cfg = transformers.Qwen3MoeConfig(
            hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, vocab_size=256, num_experts=4, num_experts_per_tok=2,
            norm_topk_prob=True, rms_norm_eps=1e-5, decoder_sparse_step=1,
            mlp_only_layers=[], max_position_embeddings=128,
            torch_dtype="float32", tie_word_embeddings=False)
        model = transformers.Qwen3MoeForCausalLM(cfg)
    model.eval()
    d = tmp_path / model_type
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


@pytest.mark.parametrize("model_type", ["mixtral", "qwen3_moe"])
def test_moe_family_matches_hf(tmp_path, model_type):
    d, hf = _save_tiny_moe(tmp_path, model_type)
    family = get_family(model_type)
    tcfg = TpuConfig(batch_size=2, seq_len=48, dtype="float32",
                     output_logits=True, enable_bucketing=False)
    icfg = family.config_cls(tcfg, load_config=load_pretrained_config(d))
    app = CausalLMApplication(d, icfg, family)
    app.load_weights().init_cache()

    rng = np.random.default_rng(0)
    ids = rng.integers(1, 256, size=(2, 10), dtype=np.int64)
    with torch.no_grad():
        golden = hf(torch.tensor(ids)).logits.numpy()
    out = app._run_prefill(ids.astype(np.int32), np.full((2,), 10, np.int32))
    np.testing.assert_allclose(np.asarray(out["logits"]), golden,
                               atol=5e-3, rtol=1e-3)

    with torch.no_grad():
        hf_seq = hf.generate(torch.tensor(ids), max_new_tokens=8,
                             do_sample=False).numpy()
    app.reset()
    res = app.generate(ids.astype(np.int32), max_new_tokens=8)
    np.testing.assert_array_equal(res["sequences"], hf_seq)


def test_moe_family_tp_ep_mesh(tmp_path):
    """Mixtral on a tp=2 x ep=2 mesh (tp_degree=4) matches single-device."""
    d, hf = _save_tiny_moe(tmp_path, "mixtral")
    family = get_family("mixtral")
    tcfg = TpuConfig(batch_size=2, seq_len=48, dtype="float32",
                     output_logits=True, enable_bucketing=False,
                     tp_degree=4, ep_degree=2)
    icfg = family.config_cls(tcfg, load_config=load_pretrained_config(d))
    app = CausalLMApplication(d, icfg, family)
    assert dict(zip(app.mesh.axis_names, app.mesh.devices.shape))[
        "ep"] == 2
    app.load_weights().init_cache()
    rng = np.random.default_rng(0)
    ids = rng.integers(1, 256, size=(2, 10), dtype=np.int64)
    with torch.no_grad():
        golden = hf(torch.tensor(ids)).logits.numpy()
    out = app._run_prefill(ids.astype(np.int32), np.full((2,), 10, np.int32))
    np.testing.assert_allclose(np.asarray(out["logits"]), golden,
                               atol=5e-3, rtol=1e-3)


def test_moe_hybrid_tkg_sharding_matches(tmp_path):
    """Hybrid CTE/TKG expert sharding (reference: moe_v2.py:135-161
    HybridShardingConfig with moe_tkg_ep_degree=1): decode re-constrains
    the expert weights all-experts-local; generation must match the
    uniform-sharding run token for token."""
    from neuronx_distributed_inference_tpu.config import MoEConfig
    d, hf = _save_tiny_moe(tmp_path, "mixtral")
    family = get_family("mixtral")

    def run(hybrid):
        mc = MoEConfig(moe_tkg_ep_degree=1) if hybrid else None
        kw = dict(batch_size=2, seq_len=48, dtype="float32",
                  output_logits=True, enable_bucketing=False,
                  tp_degree=4, ep_degree=2)
        if mc is not None:
            kw["moe_config"] = mc
        tcfg = TpuConfig(**kw)
        icfg = family.config_cls(tcfg, load_config=load_pretrained_config(d))
        app = CausalLMApplication(d, icfg, family)
        app.load_weights().init_cache()
        if hybrid:
            assert app.spec.moe.tkg_experts_local
        ids = np.random.default_rng(1).integers(1, 256, size=(2, 10),
                                                dtype=np.int64)
        return app.generate(ids.astype(np.int32), max_new_tokens=8,
                            return_logits=True)

    base = run(False)
    hyb = run(True)
    np.testing.assert_array_equal(hyb["generated"], base["generated"])
    for a, b in zip(hyb["logits"], base["logits"]):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)


def test_sparsemixer_pick_uses_its_parameters(rng):
    """Regression: the sparsemixer inner pick() once read the closed-over
    logits instead of its scores argument — correct only by accident for the
    first pass. Both passes now run through pick(scores, ref); pin the full
    two-pass semantics against an independent NumPy reference."""
    spec = _moe_spec(num_experts=8, top_k=2, router_act="sparsemixer")
    h = rng.normal(size=(2, 3, 16)).astype(np.float32)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    top_vals, top_idx = moe_mod.route(spec, jnp.asarray(h), jnp.asarray(w))

    logits = (h.reshape(-1, 16) @ w).astype(np.float32)
    eps = spec.sparsemixer_eps

    def ref_pick(scores, ref):
        mx = scores.max(-1, keepdims=True)
        factor = np.maximum(np.abs(ref), mx)
        masked = np.where((mx - ref) / factor > 2 * eps, -np.inf, scores)
        idx = scores.argmax(-1)
        e = np.exp(masked - masked.max(-1, keepdims=True))
        gates = e / e.sum(-1, keepdims=True)
        return np.take_along_axis(gates, idx[:, None], 1)[:, 0], idx

    v1, i1 = ref_pick(logits, logits)
    masked_scores = logits.copy()
    masked_scores[np.arange(len(i1)), i1] = -np.inf
    v2, i2 = ref_pick(masked_scores, logits)

    np.testing.assert_array_equal(np.asarray(top_idx).reshape(-1, 2),
                                  np.stack([i1, i2], -1))
    np.testing.assert_allclose(np.asarray(top_vals).reshape(-1, 2),
                               np.stack([v1, v2], -1), atol=1e-5)


def test_tkg_local_quantized_moe_warns_and_counts(caplog):
    """Regression: tkg_experts_local silently degrades to the prefill expert
    layout when the MoE weights are quantized; spec_from_config must say so
    loudly and bump the degradation telemetry counter."""
    import logging

    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.config import MoEConfig
    from neuronx_distributed_inference_tpu.models.mixtral.modeling_mixtral \
        import MixtralFamily, MixtralInferenceConfig
    from neuronx_distributed_inference_tpu.telemetry.metrics import \
        MOE_TKG_LOCAL_QUANT_DEGRADED_TOTAL

    hf = dict(model_type="mixtral", hidden_size=64, num_attention_heads=4,
              num_hidden_layers=2, num_key_value_heads=2, vocab_size=256,
              intermediate_size=96, rms_norm_eps=1e-5, num_local_experts=4,
              num_experts_per_tok=2, rope_theta=10000.0,
              max_position_embeddings=128, hidden_act="silu",
              tie_word_embeddings=False, torch_dtype="float32")

    def build(quantized):
        tcfg = TpuConfig(batch_size=1, seq_len=32, dtype="float32",
                         enable_bucketing=False, quantized=quantized,
                         moe_config=MoEConfig(moe_tkg_ep_degree=1))
        return MixtralFamily.build_spec(MixtralInferenceConfig(tcfg, **hf))

    reg = telemetry.MetricsRegistry()
    telemetry.set_registry(reg)
    try:
        with caplog.at_level(logging.WARNING):
            spec = build(quantized=True)
    finally:
        telemetry.disable()
    assert spec.moe.tkg_experts_local
    assert any("quantized" in r.getMessage().lower()
               and "tkg_experts_local" in r.getMessage()
               for r in caplog.records)
    assert reg.get(MOE_TKG_LOCAL_QUANT_DEGRADED_TOTAL).get() == 1

    # unquantized hybrid stays silent
    caplog.clear()
    with caplog.at_level(logging.WARNING):
        spec = build(quantized=False)
    assert spec.moe.tkg_experts_local
    assert not any("tkg_experts_local" in r.getMessage()
                   for r in caplog.records)


# ---------------------------------------------------------------------------
# the ragged path reads a layer's experts out of the stack in place (ISSUE 31)
# ---------------------------------------------------------------------------

STACK_L, STACK_E = 5, 6


def _stacked_case(rng, expert_bias, empty):
    """A toy stack (L, E, ...) and a routing of 24 tokens; ``empty`` keeps
    experts 1 and 4 out of it (their groups have no rows)."""
    spec = _moe_spec(num_experts=STACK_E, top_k=2, expert_bias=expert_bias)
    L, e, h, i = STACK_L, STACK_E, 16, 32
    f32 = np.float32

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape).astype(f32) * scale)
    x = rand(3, 8, h)
    w = [rand(L, e, h, i, scale=0.1), rand(L, e, h, i, scale=0.1),
         rand(L, e, i, h, scale=0.1)]
    b = ([rand(L, e, i), rand(L, e, i), rand(L, e, h)]
         if expert_bias else [None] * 3)
    rw = rng.normal(size=(h, e)).astype(f32)
    top_vals, top_idx = moe_mod.route(spec, x, jnp.asarray(rw))
    if empty:
        # send experts 1 and 4's token copies to their neighbours
        top_idx = jnp.where(jnp.isin(top_idx, jnp.asarray([1, 4])),
                            top_idx + 1, top_idx)
        assert not np.isin(np.asarray(top_idx), [1, 4]).any()
    return spec, x, top_vals, top_idx, w, b


@pytest.mark.parametrize("layer", [0, STACK_L // 2, STACK_L - 1])
@pytest.mark.parametrize("empty", [False, True], ids=["all", "some-empty"])
@pytest.mark.parametrize("expert_bias", [False, True], ids=["nobias", "bias"])
def test_ragged_on_the_stack_equals_the_layer_slice(rng, layer, empty,
                                                    expert_bias):
    """Bit for bit: the same groups multiply the same operands; the other
    layers' groups are empty and own no row."""
    spec, x, tv, ti, w, b = _stacked_case(rng, expert_bias, empty)
    want = moe_mod.experts_ragged(
        spec, x, tv, ti, *(a[layer] for a in w),
        *(None if a is None else a[layer] for a in b))
    got = moe_mod.experts_ragged(spec, x, tv, ti, *w, *b, layer=layer)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and with the index traced, as the layer scan hands it over (jitted
    # against jitted: a fused combine rounds its last step differently)
    want = jax.jit(lambda li: moe_mod.experts_ragged(
        spec, x, tv, ti, *(a[li] for a in w),
        *(None if a is None else a[li] for a in b)))(jnp.int32(layer))
    traced = jax.jit(lambda li: moe_mod.experts_ragged(
        spec, x, tv, ti, *w, *b, layer=li))(jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(want))


def test_moe_block_takes_a_layer_of_the_stack(rng):
    """``moe_block`` handed ``LayerOfStack`` leaves gives what it gives on
    the slices, and says so; a few-token step over experts the kernel
    declines (these carry biases) is never handed one."""
    spec, x, _, _, w, b = _stacked_case(rng, True, False)
    spec = dataclasses.replace(spec, dense_max_tokens=8)
    names = moe_mod.EXPERT_LEAVES
    stack = dict(zip(names, w + b))
    router = jnp.asarray(rng.normal(size=(16, STACK_E)).astype(np.float32))
    assert moe_mod.stack_leaves(spec, 24, stack) == names
    assert moe_mod.stack_leaves(spec, 8, stack) == ()
    li = 3
    notes = set()
    with kernel_mode.recording(notes):
        want = moe_mod.moe_block(
            spec, x, {"router": router,
                      **{k: a[li] for k, a in stack.items()}})
        got = moe_mod.moe_block(
            spec, x, {"router": router,
                      **{k: moe_mod.LayerOfStack(a, li)
                         for k, a in stack.items()}})
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert notes == {("moe_ragged", "stacked", ""),
                     ("moe_ragged", "sliced", "the caller cut the layer out")}


def _tile_stack(rng, layers=3, experts=4, h=128, i=128, dtype=np.float32):
    """Expert leaves the few-token kernel takes: whole 128-lane tiles."""
    def rand(*shape):
        return jnp.asarray((rng.normal(size=shape) * 0.1).astype(dtype))
    return {"expert_gate": rand(layers, experts, h, i),
            "expert_up": rand(layers, experts, h, i),
            "expert_down": rand(layers, experts, i, h)}


def test_moe_block_takes_a_layer_of_the_stack_for_few_tokens(rng):
    """The few-token path: a step at or under ``dense_max_tokens`` (and a
    chunk above it whose experts each expect few rows) over experts of
    whole tiles is handed its leaves in the stack, runs the
    kernel on them and counts what it read; cut out by the caller, the same
    step keeps ``experts_dense`` and says why; the two agree."""
    spec = _moe_spec(intermediate_size=128)
    stack = _tile_stack(rng)
    names = moe_mod.EXPERT_LEAVES[:3]
    assert moe_mod.stack_leaves(spec, 4, stack) == names
    assert moe_mod.stack_leaves(spec, spec.dense_max_tokens, stack) == names
    x = jnp.asarray(rng.normal(size=(4, 1, 128)).astype(np.float32))
    # every row routes where row 0 does: two of the four experts are read
    router = jnp.zeros((128, 4), jnp.float32).at[:, 1].set(0.01).at[
        :, 3].set(0.02)
    x = jnp.abs(x)
    li = 2
    notes, tally_k, tally_d = set(), [], []
    with kernel_mode.recording(notes):
        want = moe_mod.moe_block(
            spec, x, {"router": router,
                      **{k: a[li] for k, a in stack.items()}}, tally=tally_d)
        got = moe_mod.moe_block(
            spec, x, {"router": router,
                      **{k: moe_mod.LayerOfStack(a, li)
                         for k, a in stack.items()}}, tally=tally_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)
    assert notes == {("moe_decode", "pallas-interpret", "pieces=1 of 128"),
                     ("moe_decode", "xla", "the caller cut the layer out")}
    # [touched, assigned, read, picks, identity picks, rows in reach]
    assert np.asarray(tally_k[0]).tolist() == [2, 8, 2, 8, 0, 4]
    assert np.asarray(tally_d[0]).tolist() == [2, 8, 4, 8, 0, 4]  # all held


@pytest.mark.parametrize("path", ["dense", "ragged", "walk", "chunk walk"])
def test_identity_columns_add_weight_times_input(rng, path):
    """``zero_experts`` (LongCat-Flash): the router's last columns are
    identity experts. On every expert path a pick of one is a pick of an
    expert the stacks do not hold, the block adds ``(sum of the picked
    identity weights) x input`` once, and the tally counts the picks apart;
    a share of the routed experts beside them, not renormalised, x 6."""
    spec = _moe_spec(num_experts=8 + 4, zero_experts=4, held_experts=4,
                     first_expert=2, top_k=3, intermediate_size=128,
                     normalize_topk=False, routed_scaling=6.0,
                     dense_max_tokens=0 if path == "ragged" else 64)
    assert (spec.num_routed, spec.num_held, spec.holds_share) == (8, 4, True)
    stack = _tile_stack(rng)
    tokens = 160 if path == "chunk walk" else 6
    x = jnp.asarray(rng.normal(size=(1, tokens, 128)).astype(np.float32))
    router = jnp.asarray(rng.normal(size=(128, 12)).astype(np.float32))
    li = 1
    in_stack = path in ("walk", "chunk walk", "ragged")
    layer_w = {"router": router,
               **{k: moe_mod.LayerOfStack(a, li) if in_stack else a[li]
                  for k, a in stack.items()}}
    notes, tally = set(), []
    with kernel_mode.recording(notes):
        got = moe_mod.moe_block(spec, x, layer_w, tally=tally)
    sites = {n[:2] for n in notes if n[0] != "moe_share"}
    assert sites == {"dense": {("moe_decode", "xla")},
                     "ragged": {("moe_ragged", "stacked")}}.get(
        path, {("moe_decode", "pallas-interpret")})
    # by hand: softmax, top 3, x 6; held experts 2..5, identity 8..11
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, idx = jax.lax.top_k(probs, 3)
    want = jnp.zeros_like(x)
    for e in range(4):
        w = jnp.sum(jnp.where(idx == 2 + e, 6.0 * top, 0.0), -1)[..., None]
        hid = jax.nn.silu(x @ stack["expert_gate"][li, e]) \
            * (x @ stack["expert_up"][li, e])
        want = want + w * (hid @ stack["expert_down"][li, e])
    zero_w = jnp.sum(jnp.where(idx >= 8, 6.0 * top, 0.0), -1)
    assert float(zero_w.max()) > 0
    want = want + zero_w[..., None] * x
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    touched, assigned, read, picks, zero, rows = np.asarray(
        tally[0]).tolist()
    assert picks == tokens * 3 and rows == tokens
    assert zero == int((np.asarray(idx) >= 8).sum()) > 0
    assert assigned == int(((np.asarray(idx) >= 2)
                            & (np.asarray(idx) < 6)).sum())
    assert touched <= read <= 4


QWEN3_NEXT_SHARE = dict(num_experts=512, top_k=10, intermediate_size=512,
                        held_experts=128)
OLMOE = dict(num_experts=64, top_k=8, intermediate_size=1024)


@pytest.mark.parametrize("spec, layers, tokens, rows_an_expert, ragged", [
    (QWEN3_NEXT_SHARE, 12, 256, 5, False),      # its one-row chunk: the walk
    (OLMOE, 8, 256, 32, False),                 # OLMoE's: the walk too
    (QWEN3_NEXT_SHARE, 12, 8192, 160, True),    # the full-batch packs
    (OLMOE, 8, 4096, 512, True),
    (QWEN3_NEXT_SHARE, 12, 32, 0.625, False),   # a decode step
    (OLMOE, 8, 16, 2, False),
    (QWEN3_NEXT_SHARE, 12, 2048, 40, True),     # the 64-wide pack: its rows
                                                # do not fit VMEM whole
], ids=["qwen3-next-chunk", "olmoe-chunk", "qwen3-next-pack", "olmoe-pack",
        "qwen3-next-decode", "olmoe-decode", "qwen3-next-pack-w64"])
def test_who_takes_the_grouped_matmuls_is_decided_from_shapes(
        spec, layers, tokens, rows_an_expert, ragged):
    """``takes_ragged`` at the shapes of the benchmark's cells (the leaves
    as shapes only: nothing is computed): a one-row chunk is the walk's at 5
    and at 32 rows an expert, a pack - whose rows the kernel cannot hold
    whole - stays on the grouped matmuls; ``stack_leaves`` hands either
    consumer its leaves in the stack; with no stack in sight, or
    ``dense_max_tokens`` 0, a chunk is never the walk's."""
    spec = _moe_spec(normalize_topk=True, **spec)
    assert tokens * spec.top_k / spec.num_experts == rows_an_expert
    held, i = spec.num_held, spec.intermediate_size
    stack = {"expert_gate": jax.ShapeDtypeStruct((layers, held, 2048, i),
                                                 jnp.bfloat16),
             "expert_up": jax.ShapeDtypeStruct((layers, held, 2048, i),
                                               jnp.bfloat16),
             "expert_down": jax.ShapeDtypeStruct((layers, held, i, 2048),
                                                 jnp.bfloat16)}
    wg = stack["expert_gate"]
    assert moe_mod.takes_ragged(spec, tokens, wg) == ragged
    assert moe_mod.stack_leaves(spec, tokens, stack) == \
        moe_mod.EXPERT_LEAVES[:3]
    few = tokens <= spec.dense_max_tokens
    assert moe_mod.takes_ragged(spec, tokens) == (not few)
    never = dataclasses.replace(spec, dense_max_tokens=0)
    assert moe_mod.takes_ragged(never, tokens, wg)
    # leaves the kernel declines keep the grouped matmuls for a chunk
    assert moe_mod.takes_ragged(
        dataclasses.replace(spec, expert_bias=True), tokens, wg) == (not few)


def test_a_programs_expert_path_is_read_from_its_engagement_record():
    path = kernel_mode.experts_path
    assert path({("paged_decode", "pallas", "pages=4")}) == "none"
    assert path({("moe_share", "xla", "held=4 of 16"),
                 ("moe_decode", "pallas", "pieces=1 of 512")}) == "walk"
    assert path({("moe_decode", "pallas-interpret", "pieces=1 of 128 rows=256"
                  " by expert in tiles of 128")}) == "walk"
    assert path({("moe_ragged", "stacked", "")}) == "ragged"
    assert path({("moe_ragged", "sliced", "quantized")}) == "ragged"
    assert path({("moe_decode", "xla", "per-expert biases")}) == "dense"
    assert path({("moe_decode", "pallas", "pieces=1 of 512"),
                 ("moe_ragged", "stacked", "")}) == "mixed"


def _quantized(stack):
    from neuronx_distributed_inference_tpu.modules.quantization import (
        QuantSpec, quantize_tensor)
    return {k: jax.tree.map(jnp.asarray,
                            quantize_tensor(np.asarray(a), QuantSpec()))
            for k, a in stack.items()}


@pytest.mark.parametrize("over, quantized, mesh_shape, why", [
    ({}, True, None, "quantized experts"),
    ({}, False, dict(ep=2), "mesh axes wider than one: ep"),
    ({}, False, dict(tp=2), "mesh axes wider than one: tp"),
    (dict(input_scaled=True), False, None,
     "input_scaled routing scales the expert input"),
    (dict(tkg_experts_local=True), False, None,
     "tkg_experts_local re-lays the experts for decode"),
    (dict(expert_bias=True), False, None, "per-expert biases"),
    (dict(glu_style="oss_clamp"), False, None, "glu oss_clamp/silu"),
    (dict(intermediate_size=32), False, None,
     "experts of 128 x 32 are not whole 128-lane tiles"),
], ids=["quantized", "ep2", "tp2", "input_scaled", "tkg_experts_local",
        "biases", "oss_clamp", "toy-widths"])
def test_what_the_few_token_kernel_declines_keeps_the_dense_path(
        rng, cpu_devices, monkeypatch, over, quantized, mesh_shape, why):
    """Each refusal of ``moe_decode.declined`` leaves the step's leaves
    sliced (``stack_leaves`` names none), runs ``experts_dense`` and notes
    why."""
    spec = _moe_spec(**{**dict(intermediate_size=128), **over})
    stack = _tile_stack(rng, i=spec.intermediate_size)
    if spec.expert_bias:
        stack.update({k: jnp.zeros((3, 4, 128), jnp.float32)
                      for k in moe_mod.EXPERT_LEAVES[3:]})
    if quantized:
        stack = _quantized(stack)
    x = jnp.asarray(rng.normal(size=(2, 1, 128)).astype(np.float32))
    router = jnp.asarray(rng.normal(size=(128, 4)).astype(np.float32))
    mesh = contextlib.nullcontext()
    if mesh_shape:
        n = int(np.prod(list(mesh_shape.values())))
        mesh = jax.sharding.set_mesh(
            build_mesh(MeshConfig(**mesh_shape), cpu_devices[:n]))
    calls = []
    dense = moe_mod.experts_dense
    monkeypatch.setattr(moe_mod, "experts_dense",
                        lambda *a, **kw: calls.append(1) or dense(*a, **kw))
    notes = set()
    with kernel_mode.recording(notes), mesh:
        assert moe_mod.stack_leaves(spec, 2, stack) == ()
        jax.eval_shape(
            lambda x, lw: moe_mod.moe_block(spec, x, lw, phase="decode"), x,
            {"router": router, **jax.tree.map(lambda a: a[1], stack)})
    assert calls == [1]
    assert notes == {("moe_decode", "xla", why)}


OLMOE_TOY = dict(model_type="olmoe", hidden_size=64, intermediate_size=128,
                 num_hidden_layers=3, num_attention_heads=4,
                 num_key_value_heads=4, head_dim=16, vocab_size=512,
                 rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
                 tie_word_embeddings=False, torch_dtype="float32",
                 num_experts=4, num_experts_per_tok=2, norm_topk_prob=False)


@pytest.mark.parametrize("serve, rows, width, want", [
    ({}, 4, 32, ("stacked", "")),
    ({}, 4, 1, None),                       # 4 tokens: the dense path
    (dict(quantized=True, quantization_dtype="int8"), 4, 32,
     ("sliced", "quantized experts are dequantized per call")),
    (dict(tp_degree=2, ep_degree=2), 4, 32,
     ("sliced", "expert axis sharded over ep")),
], ids=["chunk", "decode", "quantized", "ep2"])
def test_the_paged_step_notes_how_its_ragged_path_reads(serve, rows, width,
                                                        want):
    """The engagement record of ISSUE 31's mechanism: an OLMoE toy's chunk
    program notes ``moe_ragged: stacked``; a quantized or ep > 1 build
    keeps the sliced form and says why; a dense-path step notes nothing."""
    fam = get_family("olmoe")
    tcfg = TpuConfig(**{**dict(
        batch_size=4, seq_len=96, dtype="float32", enable_bucketing=True,
        context_encoding_buckets=[32], is_block_kv_layout=True,
        pa_block_size=8, is_prefix_caching=False), **serve})
    app = PagedCausalLMApplication(None, fam.config_cls(tcfg, **OLMOE_TOY),
                                   fam, mesh=mesh_from_config(tcfg))
    app.init_random_weights(5).init_cache()
    i32 = jnp.int32
    ids = jax.ShapeDtypeStruct((rows, width), i32)
    notes = set()
    with kernel_mode.recording(notes), jax.sharding.set_mesh(app.mesh):
        jax.eval_shape(
            lambda p, c, a, t, n, r: model_base.paged_forward_step(
                app.spec, app.tpu_config, p, c, a, a, a, t, n, None, r),
            app.params, app.cache, ids,
            jax.ShapeDtypeStruct((rows, 12), i32),
            jax.ShapeDtypeStruct((rows,), i32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    got = {(p, why) for site, p, why in notes if site == "moe_ragged"}
    assert got == ({want} if want else set())


def test_the_scanned_decode_step_reads_the_touched_experts(monkeypatch):
    """ISSUE 37 on the layer scan: an OLMoE toy with experts of whole
    128-lane tiles runs its T = 1 paged step on the kernel (interpret mode
    here), the layer a traced scalar, and gives the logits of the same step
    on ``experts_dense``; the tally, a row a layer of the scan summed,
    counts the same routing and fewer experts read."""
    from neuronx_distributed_inference_tpu.ops import moe_decode
    fam = get_family("olmoe")
    tcfg = TpuConfig(batch_size=4, seq_len=96, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[32],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=False, output_logits=True)
    hf = dict(OLMOE_TOY, hidden_size=128, num_experts=8)
    app = PagedCausalLMApplication(None, fam.config_cls(tcfg, **hf), fam,
                                   mesh=mesh_from_config(tcfg))
    app.init_random_weights(5).init_cache()
    assert app.params["layers"]["expert_gate"].shape == (3, 8, 128, 128)
    i32 = jnp.int32
    ids = jnp.asarray([[5], [9], [5], [5]], i32)
    pos = jnp.zeros((4, 1), i32)
    # rows 0 and 1 live on blocks 1 and 2; rows 2 and 3 are pads: clones of
    # row 0 that write nowhere
    slots = jnp.asarray([[8], [16], [-1], [-1]], i32)
    table = jnp.zeros((4, 12), i32).at[:, 0].set(jnp.asarray([1, 2, 1, 1]))
    last = jnp.zeros((4,), i32)

    def step():
        notes = set()
        with kernel_mode.recording(notes), jax.sharding.set_mesh(app.mesh):
            out = model_base.paged_forward_step(
                app.spec, app.tpu_config, app.params, app.cache, ids, pos,
                slots, table, last, None, jnp.zeros((2,), jnp.uint32))
        return out, {n[1:] for n in notes if n[0] == "moe_decode"}

    got, notes = step()
    assert notes == {("pallas-interpret", "pieces=1 of 128")}
    monkeypatch.setattr(moe_decode, "declined",
                        lambda moe, wg, tokens=1: "forced")
    want, notes = step()
    assert notes == {("xla", "forced")}
    np.testing.assert_allclose(np.asarray(got["logits"]),
                               np.asarray(want["logits"]), atol=2e-5, rtol=0)
    touched, assigned, read, picks, zero, rows = np.asarray(
        got["moe_tally"]).tolist()
    assert np.asarray(want["moe_tally"]).tolist() == [touched, assigned,
                                                      3 * 8, picks, zero,
                                                      rows]
    assert rows == 3 * 2       # no groups: every live row, every layer
    assert (picks, zero) == (assigned, 0)    # every expert is held here
    assert assigned == 3 * 2 * 2             # layers x live rows x top-k
    assert 0 < touched <= read < 3 * 8
    # the pads are clones of row 0: the same logits, bit for bit
    np.testing.assert_array_equal(np.asarray(got["logits"])[2],
                                  np.asarray(got["logits"])[0])
