"""The layer walks of ``models/model_base.py`` share one scan and one return
shape (ISSUE 58).

Traced only (``jax.make_jaxpr`` over shapes, nothing compiled, no weights):
the toy stacks are the fixtures of the families' own paged tests, built as
``tests/test_chip_aot.py`` builds the cells' (``_serving_shapes``), on the
CPU device.

* the four SCANNED walks (``run_layer_slice``, and with a learned sparse
  selection; ``run_layers_shortcut``; ``run_layers_window``) go through
  ``scan_layers``: the leaves ``moe.stack_leaves`` names reach ``moe_block``
  as the whole stack, nothing slices them a layer at a time, the routing
  tally counts the live rows of a T = 1 step and comes back a row a step;
* ``run_layers`` returns (hidden, cache, per-layer outputs) on every
  dispatch branch, the cache with the keys it was given.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest

import test_deepseek_v3_paged as deepseek_toy   # puts benchmark/ on sys.path
import test_keye_vl2_paged as keye_toy
import test_longcat_flash_paged as longcat_toy
import test_recurrent_paged as granite_toy
import test_smallthinker_paged as smallthinker_toy
from test_chip_aot import _serving_shapes
from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.modules import moe as moe_mod

#: OLMoE's keys at a toy size: a plain stack of expert layers
OLMOE_TOY = dict(
    model_type="olmoe", hidden_size=64, intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
    vocab_size=128, rms_norm_eps=1e-5, rope_theta=10000.0,
    max_position_embeddings=512, hidden_act="silu",
    tie_word_embeddings=False)
LLAMA_TOY = dict(
    model_type="llama", hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=128, rms_norm_eps=1e-5, rope_theta=10000.0,
    max_position_embeddings=512, hidden_act="silu",
    tie_word_embeddings=False)


def _pool(toy):
    return {k: toy.SERVE[k] for k in (
        "batch_size", "seq_len", "pa_block_size", "pa_num_blocks",
        "context_encoding_buckets")}, toy.SERVE["is_prefix_caching"]


#: name: (config keys, its depth, (pool, prefix caching), the stack that
#: holds the expert leaves, scan steps of a paged step)
STACKS = {
    "experts": (OLMOE_TOY, 3, _pool(keye_toy), "layers", 3),
    "sparse": (keye_toy.HF, 2, _pool(keye_toy), "layers", 2),
    "sub_blocks": (longcat_toy.HF, 2, _pool(longcat_toy), "moe_layers", 2),
    "window_pool": (smallthinker_toy.HF, 8, _pool(smallthinker_toy),
                    "layers", 2),
    "recurrent": (granite_toy.HF, None, _pool(granite_toy), None, None),
    "first_dense": (deepseek_toy.HF, 3, _pool(deepseek_toy), "moe_layers",
                    None),
    "dense": (LLAMA_TOY, 2, _pool(keye_toy), None, None),
}


def _traced_step(name, width, monkeypatch):
    """Trace ``paged_forward_step`` of ``STACKS[name]`` at ``width`` tokens a
    row with ``moe_block``, ``moe.stack_leaves``, ``scan_layers`` and
    ``run_layers`` watched. Returns (what was seen, the jaxpr, params)."""
    hf, layers, (serve, prefix), _, _ = STACKS[name]
    depth = "num_layers" if "num_layers" in hf else "num_hidden_layers"
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        hf, layers or hf[depth], 1, jax.devices()[:1], serve, prefix=prefix)
    seen = {"moe": [], "named": 0, "scans": 0, "walks": [], "cache": cache}

    def named(moe, tokens, stack):
        # every expert leaf, as the chip names them where its kernels engage
        seen["named"] += 1
        return tuple(k for k in moe_mod.EXPERT_LEAVES if k in stack)

    def moe_spy(moe, x, layer_w, *, tally=None, live=None, **_):
        seen["moe"].append((layer_w["expert_gate"], live))
        if tally is not None:
            tally.append(jnp.ones((7,), jnp.int32))
        return jnp.zeros_like(x)

    def watched(fn, count):
        def call(*a, **kw):
            out = fn(*a, **kw)
            if count == "walks":
                seen["walks"].append(out)
            else:
                seen[count] += 1
            return out
        return call

    monkeypatch.setattr(moe_mod, "stack_leaves", named)
    monkeypatch.setattr(model_base, "moe_block", moe_spy)
    monkeypatch.setattr(model_base, "scan_layers",
                        watched(model_base.scan_layers, "scans"))
    monkeypatch.setattr(model_base, "run_layers",
                        watched(model_base.run_layers, "walks"))
    rows = tcfg.batch_size
    i32 = jnp.int32
    with jax.sharding.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(
            partial(model_base.paged_forward_step, spec, tcfg))(
            params, cache, *(sds((rows, width), i32),) * 3,
            sds((rows, mb), i32), sds((rows,), i32), None,
            sds((2,), jnp.uint32))
    return seen, jaxpr, params


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("name", ["experts", "sparse", "sub_blocks",
                                  "window_pool"])
def test_a_scanned_walk_leaves_the_named_leaves_in_their_stack(
        name, monkeypatch):
    """A paged T = 1 step of each scanned walk: ``moe.stack_leaves`` is
    asked once, by ``scan_layers``; ``moe_block`` is handed every named leaf
    as a ``LayerOfStack`` of the WHOLE stack and the step's live rows; no
    scan takes an expert stack as ``xs`` and nothing slices one; the tally
    comes back a row a scan step."""
    _, _, _, stack, steps = STACKS[name]
    seen, jaxpr, params = _traced_step(name, 1, monkeypatch)
    assert seen["scans"] == 1 and seen["named"] == 1
    whole = params[stack]["expert_gate"].shape
    assert seen["moe"]
    for leaf, live in seen["moe"]:
        assert isinstance(leaf, moe_mod.LayerOfStack), type(leaf)
        assert leaf.stack.shape == whole
        assert live is not None and live.shape[1] == 1
    stacks = {params[stack][k].shape for k in moe_mod.EXPERT_LEAVES
              if k in params[stack]}
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "scan":
            first = eqn.params["num_consts"] + eqn.params["num_carry"]
            assert not stacks & {v.aval.shape for v in eqn.invars[first:]}
        if eqn.primitive.name in ("dynamic_slice", "slice", "gather"):
            assert eqn.invars[0].aval.shape not in stacks, eqn
    (_, _, caps), = seen["walks"]
    assert caps["moe_tally"].shape == (steps, 7)


def test_a_chunk_counts_no_routing(monkeypatch):
    """Wider than one token a row nothing is counted: no ``live`` rows, no
    ``moe_tally`` among the per-layer outputs."""
    seen, _, _ = _traced_step("experts", 8, monkeypatch)
    assert all(live is None for _, live in seen["moe"])
    (_, _, caps), = seen["walks"]
    assert caps == {}


@pytest.mark.parametrize("name", list(STACKS))
@pytest.mark.parametrize("width", [1, 8])
def test_run_layers_returns_three_things_on_every_branch(name, width,
                                                         monkeypatch):
    """(hidden, cache, per-layer outputs) whichever walk the stack takes:
    sub-blocks, recurrent, a window pool, a learned sparse selection, a
    dense run before the expert layers, plain stacks; the cache comes back
    with the keys and shapes it went in with."""
    seen, _, _ = _traced_step(name, width, monkeypatch)
    (out,), cache = seen["walks"], seen["cache"]
    assert len(out) == 3
    hidden, new_cache, caps = out
    assert hidden.ndim == 3 and hidden.shape[1] == width
    assert isinstance(caps, dict)
    assert ({k: (a.shape, a.dtype) for k, a in new_cache.items()}
            == {k: (a.shape, a.dtype) for k, a in cache.items()})


@pytest.mark.parametrize("phase", ["prefill", "decode", "decode_loop"])
def test_the_contiguous_steps_walk_the_same_way(phase, monkeypatch):
    """The contiguous cache's prefill, its unrolled T = 1 step and the fused
    loop of such steps (``decode_loop``: ``decode_chunk_tokens`` steps a
    device call, one scan whatever the geometry)."""
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu.modules import kv_cache as kv
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    family = get_family("llama")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", batch_size=2, seq_len=32)
    spec = family.build_spec(family.config_cls(tcfg, **LLAMA_TOY),
                             tp_degree=1)
    sds = jax.ShapeDtypeStruct
    params = jax.tree.map(lambda ps: sds(ps.shape, ps.dtype),
                          model_base.decoder_param_specs(spec),
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    cache = jax.eval_shape(lambda: kv.init_cache(kv.KVCacheSpec(
        num_layers=2, batch_size=2, max_seq_len=32, num_kv_heads=2,
        head_dim=16, dtype=jnp.float32)))
    walks = []
    real = model_base.run_layers
    monkeypatch.setattr(
        model_base, "run_layers",
        lambda *a, **kw: walks.append(real(*a, **kw)) or walks[-1])
    i32 = jnp.int32
    rng = sds((2,), jnp.uint32)
    if phase == "prefill":
        out = jax.eval_shape(
            partial(model_base.context_encoding_step, spec, tcfg), params,
            cache, sds((2, 16), i32), sds((2, 16), i32), sds((2,), i32),
            sds((2,), i32), None, rng)
    elif phase == "decode":
        out = jax.eval_shape(
            partial(model_base.token_generation_step, spec, tcfg), params,
            cache, sds((2, 1), i32), sds((2, 1), i32), sds((2,), i32), None,
            rng)
    else:
        out = jax.eval_shape(
            partial(model_base.decode_loop, spec, tcfg, num_steps=4), params,
            cache, sds((2,), i32), sds((2,), i32), sds((2,), i32), None, rng)
        assert out["tokens"].shape == (2, 4)
    (walk,) = walks
    assert len(walk) == 3 and set(walk[1]) == set(cache)
    assert jax.tree.map(lambda a: a.shape, out["cache"]) \
        == jax.tree.map(lambda a: a.shape, cache)
