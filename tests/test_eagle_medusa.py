"""EAGLE + Medusa speculation tests (reference: NeuronFusedSpecModel EAGLE
paths model_base.py:1931-2754, medusa submodel, modules/eagle/token_tree.py).

The gold property: greedy speculation is LOSSLESS — emitted tokens must be
identical to plain greedy decoding of the target, regardless of draft/head
quality (random weights here)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from neuronx_distributed_inference_tpu.config import (SpeculationConfig,
                                                      TpuConfig)
from neuronx_distributed_inference_tpu.models import model_base, speculation
from neuronx_distributed_inference_tpu.models.application import \
    CausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.modules.kv_cache import (KVCacheSpec,
                                                                init_cache)
from neuronx_distributed_inference_tpu.modules.token_tree import (DEFAULT_TREE,
                                                                  TokenTree)
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh)

from conftest import tiny_llama_hf_config


def _target_app(seq_len=96, spec_cfg=None, medusa_heads=0, **tcfg_over):
    tcfg = TpuConfig(batch_size=2, seq_len=seq_len, dtype="float32",
                     enable_bucketing=False, speculation_config=spec_cfg,
                     **tcfg_over)
    icfg = LlamaInferenceConfig(tcfg, **tiny_llama_hf_config())
    mesh = build_mesh(MeshConfig(tp=1))
    app = CausalLMApplication(None, icfg, LlamaFamily, mesh=mesh)
    if medusa_heads:
        import dataclasses
        app.spec = dataclasses.replace(app.spec, medusa_heads=medusa_heads)
    app.init_random_weights(seed=0)
    app.init_cache()
    return app


def _plain_greedy(prompts, n, seq_len=96):
    app = _target_app(seq_len=seq_len)
    out = app.generate(prompts, max_new_tokens=n)
    return out["generated"]


def test_eagle_matches_plain_greedy(rng):
    prompts = rng.integers(1, 500, size=(2, 10)).astype(np.int32)
    golden = _plain_greedy(prompts, 16)

    spec_cfg = SpeculationConfig(speculation_length=3)
    target = _target_app(spec_cfg=spec_cfg, output_full_hidden=True)
    # tiny 2-layer EAGLE draft sharing the target's architecture family
    draft_spec = model_base.spec_from_config(
        target.config, tp_degree=1, num_layers=2)
    draft_params = speculation.init_eagle_draft_params(
        draft_spec, jax.random.PRNGKey(7), target.mesh)
    draft_cache = init_cache(KVCacheSpec(
        num_layers=2, batch_size=2, max_seq_len=96,
        num_kv_heads=draft_spec.gqa.num_kv_heads,
        head_dim=draft_spec.head_dim, dtype=draft_spec.kv_dtype), target.mesh)
    dec = speculation.EagleDecoder(target, draft_spec, draft_params,
                                   draft_cache)
    out = dec.generate(prompts, max_new_tokens=16)
    np.testing.assert_array_equal(out["generated"], golden)
    assert out["mean_tokens_per_step"] >= 1.0


def test_eagle_draft_input_norm_variant(rng):
    prompts = rng.integers(1, 500, size=(2, 8)).astype(np.int32)
    golden = _plain_greedy(prompts, 8)
    spec_cfg = SpeculationConfig(speculation_length=2)
    target = _target_app(spec_cfg=spec_cfg, output_full_hidden=True)
    draft_spec = model_base.spec_from_config(target.config, tp_degree=1,
                                             num_layers=1)
    draft_params = speculation.init_eagle_draft_params(
        draft_spec, jax.random.PRNGKey(3), target.mesh, input_norm=True)
    draft_cache = init_cache(KVCacheSpec(
        num_layers=1, batch_size=2, max_seq_len=96,
        num_kv_heads=draft_spec.gqa.num_kv_heads,
        head_dim=draft_spec.head_dim, dtype=draft_spec.kv_dtype), target.mesh)
    dec = speculation.EagleDecoder(target, draft_spec, draft_params,
                                   draft_cache, input_norm=True)
    out = dec.generate(prompts, max_new_tokens=8)
    np.testing.assert_array_equal(out["generated"], golden)


def test_medusa_matches_plain_greedy(rng):
    prompts = rng.integers(1, 500, size=(2, 10)).astype(np.int32)
    golden = _plain_greedy(prompts, 16)
    spec_cfg = SpeculationConfig(medusa_speculation_length=4)
    target = _target_app(spec_cfg=spec_cfg, medusa_heads=3)
    dec = speculation.MedusaDecoder(target)
    out = dec.generate(prompts, max_new_tokens=16)
    np.testing.assert_array_equal(out["generated"], golden)
    assert out["mean_tokens_per_step"] >= 1.0


def test_token_tree_structure():
    tree = TokenTree(DEFAULT_TREE)
    # root + 7 config nodes
    assert tree.num_nodes == 8
    assert tree.max_depth == 3
    assert tree.depth.tolist() == [0, 1, 1, 1, 2, 2, 2, 3]
    # node ordering: (), (0), (1), (2), (0,0), (0,1), (1,0), (0,0,0)
    assert tree.parent.tolist() == [-1, 0, 0, 0, 1, 1, 2, 4]
    # every node attends itself and its ancestors only
    anc = tree.ancestor_mask
    assert anc[7].tolist() == [True, True, False, False, True, False, False,
                               True]
    assert tree.level_widths.tolist() == [3, 2, 1]
    paths, lens = tree.leaf_path_matrix()
    assert paths.shape == (8, 4)
    assert lens.max() == 4


def test_token_tree_attention_mask():
    tree = TokenTree([[0], [1], [0, 0]])
    base = np.array([4, 2])
    mask = tree.attention_mask(base, cache_len=12)
    assert mask.shape == (2, 4, 12)
    # every node sees the committed prefix
    assert mask[0, :, :4].all() and mask[1, :, :2].all()
    # node 3 = (0,0): slot base+3 sees root slot (base), node1 slot (base+1),
    # itself (base+3), not node2 (base+2)
    assert mask[0, 3, 4] and mask[0, 3, 5] and mask[0, 3, 7]
    assert not mask[0, 3, 6]
    # nothing beyond the tree slots
    assert not mask[0, :, 8:].any()


def test_token_tree_requires_parents():
    with pytest.raises(ValueError):
        TokenTree([[0, 0]])  # parent [0] missing


def test_medusa_tree_matches_plain_greedy(rng):
    prompts = rng.integers(1, 500, size=(2, 10)).astype(np.int32)
    golden = _plain_greedy(prompts, 16)
    spec_cfg = SpeculationConfig(medusa_speculation_length=4,
                                 token_tree_config={"paths": DEFAULT_TREE})
    target = _target_app(spec_cfg=spec_cfg, medusa_heads=3)
    dec = speculation.MedusaTreeDecoder(target)
    out = dec.generate(prompts, max_new_tokens=16)
    np.testing.assert_array_equal(out["generated"], golden)
    assert out["mean_tokens_per_step"] >= 1.0


def test_dynamic_tree_matches_plain_greedy(rng):
    """Dynamic token tree (reference: modules/eagle/dynamic_token_tree.py —
    EAGLE-2-style top-N-by-joint-logprob node selection over the proposal
    lattice): emitted tokens must equal plain greedy decode."""
    from neuronx_distributed_inference_tpu.models.speculation import (
        DynamicTreeDecoder, build_lattice)
    dep, par, br, anc, path = build_lattice(3, 2)
    assert dep.shape[0] == 1 + 3 + 9
    assert anc[4, 1] and not anc[4, 2]     # node 4 = child of node 1
    prompts = rng.integers(1, 500, size=(2, 10)).astype(np.int32)
    golden = _plain_greedy(prompts, 16)
    spec_cfg = SpeculationConfig(medusa_speculation_length=4)
    target = _target_app(spec_cfg=spec_cfg, medusa_heads=3)
    dec = DynamicTreeDecoder(target, branch_k=3, num_nodes=10)
    out = dec.generate(prompts, max_new_tokens=16)
    np.testing.assert_array_equal(out["generated"], golden)
    assert out["mean_accept"] >= 1.0


def test_data_parallel_sampler_matches_global():
    """sample_dp (reference: DataParallelSampler, sampling.py:467-578):
    batch-sharded top-k over the dp axis equals the global sampler."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from neuronx_distributed_inference_tpu.config import OnDeviceSamplingConfig
    from neuronx_distributed_inference_tpu.ops import sampling as S
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    logits = jnp.asarray(
        np.random.default_rng(0).standard_normal((8, 64)), jnp.float32)
    sp = S.prepare_sampling_params(8, top_k=4, top_p=0.9, temperature=1.0)
    cfg = OnDeviceSamplingConfig(do_sample=True, deterministic=True)
    with jax.sharding.set_mesh(mesh):
        got = jax.jit(lambda lg, s: S.sample_dp(lg, cfg, s, None))(
            logits, jnp.asarray(sp))
    want = S.sample(logits, cfg, jnp.asarray(sp), None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _eagle_draft(target, layers=2, seed=7):
    draft_spec = model_base.spec_from_config(
        target.config, tp_degree=1, num_layers=layers)
    draft_params = speculation.init_eagle_draft_params(
        draft_spec, jax.random.PRNGKey(seed), target.mesh)
    draft_cache = init_cache(KVCacheSpec(
        num_layers=layers, batch_size=2, max_seq_len=96,
        num_kv_heads=draft_spec.gqa.num_kv_heads,
        head_dim=draft_spec.head_dim, dtype=draft_spec.kv_dtype), target.mesh)
    return draft_spec, draft_params, draft_cache


def test_eagle_tree_matches_plain_greedy(rng):
    """EAGLE token-tree speculation is LOSSLESS under greedy acceptance
    (reference: EAGLE token-tree, model_base.py:2094-2515)."""
    prompts = rng.integers(1, 500, size=(2, 10)).astype(np.int32)
    golden = _plain_greedy(prompts, 16)
    spec_cfg = SpeculationConfig(speculation_length=3)
    target = _target_app(spec_cfg=spec_cfg, output_full_hidden=True)
    draft_spec, draft_params, draft_cache = _eagle_draft(target)
    dec = speculation.EagleTreeDecoder(
        target, draft_spec, draft_params, draft_cache,
        depth=3, branch_k=3, num_nodes=10)
    out = dec.generate(prompts, max_new_tokens=16)
    np.testing.assert_array_equal(out["generated"], golden)
    assert out["mean_tokens_per_step"] >= 1.0


def test_eagle_tree_accepts_at_least_chain(rng):
    """With an informative draft (the target's own stack reading the fused
    feature), the dynamic tree's top-k alternatives can only add acceptance
    opportunities over the chain draft's single greedy path."""
    prompts = rng.integers(1, 500, size=(2, 10)).astype(np.int32)
    spec_cfg = SpeculationConfig(speculation_length=3)

    def informative_draft(target):
        # draft = full target stack; fc routes the token embedding straight
        # through (h0 = embed) so the draft IS the target -> partial-to-high
        # acceptance instead of the random-draft floor
        import numpy as _np
        draft_spec = model_base.spec_from_config(target.config, tp_degree=1)
        H = draft_spec.hidden_size
        draft_params = dict(target.params)
        fc = _np.zeros((2 * H, H), _np.float32)
        fc[:H] = _np.eye(H)
        draft_params["fc"] = jnp.asarray(fc)
        draft_cache = init_cache(KVCacheSpec(
            num_layers=draft_spec.num_layers, batch_size=2, max_seq_len=96,
            num_kv_heads=draft_spec.gqa.num_kv_heads,
            head_dim=draft_spec.head_dim, dtype=draft_spec.kv_dtype),
            target.mesh)
        return draft_spec, draft_params, draft_cache

    t1 = _target_app(spec_cfg=spec_cfg, output_full_hidden=True)
    dspec, dparams, dcache = informative_draft(t1)
    chain = speculation.EagleDecoder(t1, dspec, dparams, dcache)
    out_c = chain.generate(prompts, max_new_tokens=16)

    t2 = _target_app(spec_cfg=spec_cfg, output_full_hidden=True)
    dspec, dparams, dcache = informative_draft(t2)
    tree = speculation.EagleTreeDecoder(t2, dspec, dparams, dcache,
                                        depth=3, branch_k=3, num_nodes=10)
    out_t = tree.generate(prompts, max_new_tokens=16)

    np.testing.assert_array_equal(out_t["generated"], out_c["generated"])
    assert (out_t["mean_tokens_per_step"]
            >= out_c["mean_tokens_per_step"] - 1e-9), (
        out_t["mean_tokens_per_step"], out_c["mean_tokens_per_step"])
    assert out_t["mean_tokens_per_step"] > 1.5   # informative draft accepts
