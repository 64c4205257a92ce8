"""Qwen3-Next on the paged serving path (ISSUE 36): one chip's share of the
expert layers on the recurrent walk, key heads shared by groups of value
heads in the gated delta rule, gated attention.

``qwen3_next`` (three delta-rule layers to one gated-attention layer, every
MLP the sparse block; the architecture of Qwen/Qwen3-Next-80B-A3B-Instruct)
served through ``PagedEngineAdapter`` with default arguments, at a toy size
on the CPU in float32, in ``tests/test_olmo_hybrid_paged.py``'s manner:
every test holds the LOGITS of the served path, at every position a
dispatch computed, to the plain reference
``benchmark/references/qwen3_next.py`` (token-by-token recurrence, held to
``transformers``' ``Qwen3NextForCausalLM`` by ``benchmark/tests/
test_reference.py``), both holding the SAME share: experts 4..7 of 16.

  (a) a prompt walked in three chunks through the ONE-ROW program with
      ``state_slots``, a padded last chunk, then decode through the KV pool
      and the state slots; on the dense expert path and on the ragged one;
  (b) prompts packed as rows of one full-batch dispatch (the ragged path
      over the share, its layer read out of the stack in place) beside a
      decoding row, whose slot is a dead row of the pack: left bit for bit;
  (c) the controls of the benchmark's gate and a bf16-carried state each
      fail (a)'s comparison;
  (d) the four shares of an expert layer, the gated shared expert counted
      once, add up to the uncut reference's layer, on both expert paths;

and the edges: the loader's interleaved ``in_proj_qkvz`` / ``in_proj_ba``,
the exact counts of a decode step's routing, the family's refusals, the
warm-up plan and what the engagement record says of the share.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from harness import build, weights  # noqa: E402

from neuronx_distributed_inference_tpu import telemetry  # noqa: E402
from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.modules import moe, ssm  # noqa: E402
from neuronx_distributed_inference_tpu.ops import kernel_mode  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import \
    precompile  # noqa: E402
from neuronx_distributed_inference_tpu.telemetry import \
    metrics as tmetrics  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

#: one period at a toy size: every key of the published config.json, and the
#: share: the weights hold experts 4..7 of the 16 the router scores
HF = dict(
    model_type="qwen3_next", vocab_size=128, hidden_size=32,
    intermediate_size=80, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, hidden_act="silu",
    max_position_embeddings=512, attention_bias=False, rms_norm_eps=1e-6,
    tie_word_embeddings=False, full_attention_interval=4,
    layer_types=["linear_attention", "linear_attention", "linear_attention",
                 "full_attention"],
    partial_rotary_factor=0.25, rope_theta=10000000, rope_scaling=None,
    use_sliding_window=False, decoder_sparse_step=1, mlp_only_layers=[],
    num_experts=4, router_num_experts=16, first_expert=4,
    num_experts_per_tok=4, norm_topk_prob=True, moe_intermediate_size=16,
    shared_expert_intermediate_size=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4)
BATCH = 4
#: a pack of 4 rows x 32 is 128 tokens, over ``dense_max_tokens`` (64): the
#: ragged path; a one-row chunk and a decode step run the dense one
SERVE = dict(batch_size=BATCH, seq_len=128, pa_block_size=8, pa_num_blocks=64,
             context_encoding_buckets=[8, 32], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(36)
#: 69 = 32 + 32 + 5: three chunks, the last one padded to the 8 bucket
P69, Q45, R21, S12 = (RNG.integers(1, 128, size=n).tolist()
                      for n in (69, 45, 21, 12))
#: float32 on both sides: the served logits (|logit| up to ~2) agree with
#: the reference's to a few 1e-6; the controls move them by 1e-2 and more
ATOL = 2e-4


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("qwen3_next")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 36)


def _app(ref, w, hf=HF, **serve):
    family = get_family("qwen3_next")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


def _want(ref, w, tokens, hf=HF):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens])))[0]


def _error(tap, ref, w, sid, prompt, stream):
    fed = prompt + stream[:-1]
    return float(np.abs(tap.logits(sid, len(fed))
                        - _want(ref, w, fed)).max())


def _check(tap, ref, w, sid, prompt, stream):
    assert _error(tap, ref, w, sid, prompt, stream) < ATOL
    want = _want(ref, w, prompt + stream[:-1])
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _serve_p69(app, decode=6):
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    _decode(ad, [7], stream, decode)
    return ad, tap, stream[7]


def _respec(monkeypatch, **fields):
    """The family's spec with fields replaced; a dict value replaces fields
    of the sub-spec of that name (``moe``, ``ssm``)."""
    family = get_family("qwen3_next")
    build_spec = family.build_spec.__func__

    def respec(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        return dataclasses.replace(spec, **{
            k: (dataclasses.replace(getattr(spec, k), **v)
                if isinstance(v, dict) else v) for k, v in fields.items()})
    monkeypatch.setattr(family, "build_spec", classmethod(respec))


@pytest.mark.parametrize("experts", ["dense", "ragged"])
def test_a_three_chunks_with_a_padded_last_one_then_decode(
        ref, gate_weights, monkeypatch, experts):
    if experts == "ragged":
        # every dispatch, the decode step too, over the sorted grouped
        # matmuls: assignments to absent experts dropped before the sort
        _respec(monkeypatch, moe=dict(dense_max_tokens=0))
    app = _app(ref, gate_weights)
    ad, tap, stream = _serve_p69(app)
    assert tap.shapes == [(1, 32), (1, 32), (1, 8)] + [(BATCH, 1)] * 6
    _check(tap, ref, gate_weights, 7, P69, stream)
    # the slot holds the reference's state after the last token fed
    got = np.asarray(app.cache["ssm"][:, ad._state_slot[7]])
    want = np.asarray(ref.final_states(
        HF, gate_weights, jnp.asarray([P69 + stream[:-1]])))[:, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    paths = {k["site"]: k for k in app.warmup_state()["kernels"]
             if k["site"].startswith("moe")}
    assert paths["moe_share"]["reason"] == "held=4 of 16 from 4 top_k=4"
    assert ("moe_ragged" in paths) == (experts == "ragged")
    if experts == "ragged":
        assert paths["moe_ragged"]["path"] == "stacked"


#: the same period with a tile the state-step kernel takes (ISSUE 44):
#: (8, 64) where HF's (8, 16) keeps the XLA step; two key heads serve four
#: value heads, each reading its key head through the kernel's index
HF_KERNEL = dict(HF, linear_value_head_dim=64)


def test_a_chunks_then_decode_on_the_state_kernel(ref):
    """Three chunks through the chunked form, then nine decode steps on the
    state-step kernel with GROUPED key heads: the logits at every position
    and the slot's final state are the float32 reference's, and the
    adapter counted every decode dispatch on the kernel."""
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 44)
    app = _app(ref, w, hf=HF_KERNEL)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = [ad.add_requests([7], [P69])[7]]
    _decode(ad, [7], {7: stream}, 9)
    assert tap.shapes == [(1, 32), (1, 32), (1, 8)] + [(BATCH, 1)] * 9
    fed = P69 + stream[:-1]
    want = _want(ref, w, fed, HF_KERNEL)
    assert float(np.abs(tap.logits(7, len(fed)) - want).max()) < ATOL
    assert stream == want[len(P69) - 1:].argmax(-1).tolist()
    assert app.cache["ssm"].dtype == jnp.float32
    got = np.asarray(app.cache["ssm"][:, ad._state_slot[7]])
    want = np.asarray(ref.final_states(
        HF_KERNEL, w, jnp.asarray([fed])))[:, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    step = {k[0]: k[1:] for k in app.paged_program_notes(BATCH, 1)}
    assert step["recurrent_state"][0] == "pallas-interpret"
    assert step["recurrent_state"][1].endswith(" heads=4 tile=8x64")
    chunk = {k[0]: k[1:] for k in app.paged_program_notes(1, 32)}
    assert chunk["recurrent_state"][0] == "xla"
    assert (ad.host_stats["dispatches_state_kernel"],
            ad.host_stats["dispatches"]) == (9, 9)


def test_b_prompts_packed_beside_a_decoding_row(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    slot1 = ad._state_slot[1]
    before = {k: np.asarray(app.cache[k][:, slot1])
              for k in ("conv_x", "ssm")}
    n0 = len(tap.shapes)
    # packed at the full batch, 4 x 32 = 128 tokens: the ragged path with
    # its layer read in place; the rest of sequence 2 in the one-row program
    first = ad.add_requests([2, 3], [Q45, S12])
    assert tap.shapes[n0:] == [(BATCH, 32), (1, 32)]
    assert {"site": "moe_ragged", "path": "stacked", "reason": ""} in \
        app.warmup_state()["kernels"]
    for k, was in before.items():
        np.testing.assert_array_equal(np.asarray(app.cache[k][:, slot1]),
                                      was)
        assert np.abs(was).max() > 0
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 4)
    for sid, prompt in ((1, R21), (2, Q45), (3, S12)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


def test_a_pack_less_than_half_full_goes_one_row_at_a_time(ref,
                                                           gate_weights):
    """At 8 rows, two prompts admitted at once would fill a quarter of the
    full-batch pack, which computes all 8 rows whatever it carries: each
    goes through the one-row program instead, in admission order (its
    chunks first, then the next prompt's); four prompts fill half and are
    packed. The logits are the reference's either way."""
    app = _app(ref, gate_weights, batch_size=8, pa_num_blocks=128)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    first = ad.add_requests([1, 2], [Q45, S12])
    assert tap.shapes == [(1, 32), (1, 32), (1, 32)]   # 32 + 13, then 12
    assert ad.host_stats["prefill_padded_tokens"] == 3 * 32
    n0 = len(tap.shapes)
    first.update(ad.add_requests([3, 4, 5, 6], [R21, S12, P69, Q45]))
    assert tap.shapes[n0] == (8, 32)                   # half full: packed
    stream = {s: [t] for s, t in first.items()}
    _decode(ad, None, stream, 2)
    for sid, prompt in ((1, Q45), (2, S12), (3, R21), (5, P69)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


def test_served_through_the_front_door(ref, gate_weights):
    """Two ``POST /v1/generate`` requests at once through ``ServingFrontend``
    -> ``ServingEngine`` -> ``PagedEngineAdapter()``: every SSE token is the
    reference's greedy choice, and the engine's lookahead fetched a step's
    routing counts with its tokens."""
    import asyncio
    import json

    from neuronx_distributed_inference_tpu.serving.engine import (
        ServingEngine, ServingFrontend)
    app = _app(ref, gate_weights)
    prompts, n_new = {"a": Q45, "b": S12}, 6
    adapter = PagedEngineAdapter(app)

    async def generate(host, port, prompt):
        body = json.dumps({"prompt": prompt, "max_new_tokens": n_new}).encode()
        r, w = await asyncio.open_connection(host, port)
        w.write(b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                + str(len(body)).encode() + b"\r\n\r\n" + body)
        await w.drain()
        resp = (await asyncio.wait_for(r.read(), timeout=120)).decode()
        w.close()
        events = [json.loads(line[6:]) for line in resp.splitlines()
                  if line.startswith("data: ")]
        assert events[-1]["done"] and events[-1]["reason"] == "length"
        return [e["token"] for e in events[:-1]]

    async def main():
        fe = ServingFrontend(ServingEngine(adapter))
        host, port = await fe.start()
        try:
            return await asyncio.gather(*(generate(host, port, p)
                                          for p in prompts.values()))
        finally:
            await fe.stop()

    streams = dict(zip(prompts, asyncio.run(main())))
    for name, prompt in prompts.items():
        want = _want(ref, gate_weights, prompt + streams[name][:-1])
        assert streams[name] == want[len(prompt) - 1:].argmax(-1).tolist()
    st = adapter.host_stats
    assert st["overlapped_dispatches"] > 0
    # counted where a step's tokens are fetched: the lookahead step behind
    # the last token is dropped unfetched, and counts nothing
    assert st["moe_expert_slots"] == 4 * 4 * st["blocking_fetches"]
    assert st["blocking_fetches"] in (st["device_steps"],
                                      st["device_steps"] - 1)
    assert 0 < st["moe_experts_touched"] <= st["moe_expert_slots"]


# ---------------------------------------------------------------------------
# a few wide heads share a slot of the pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads, lanes, page, page_tp4", [
    (2, 256, (1, 512), (2, 256)), (4, 128, (1, 512), (4, 128)),
    (7, 128, (1, 896), (7, 128)), (1, 256, (1, 256), (1, 256)),
    (8, 128, (8, 128), (4, 256)), (2, 64, (1, 128), (2, 64)),
    (16, 128, (16, 128), (4, 512)), (30, 128, (32, 128), (30, 128))])
def test_a_few_heads_of_whole_vregs_share_a_slot_of_the_pool(heads, lanes,
                                                             page, page_tp4):
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import \
        pool_page
    from neuronx_distributed_inference_tpu.ops.decode_attention import \
        paged_pool_fold
    assert pool_page(heads, lanes) == page
    # a shard's heads fold, never heads of two shards; none are padded
    assert pool_page(heads, lanes, tp=4) == page_tp4
    # stored as the decode kernel reads it
    if heads <= 16:
        assert paged_pool_fold(heads, lanes) == page[1] // lanes
    if heads % 4 == 0:
        assert paged_pool_fold(heads // 4, lanes) == page_tp4[1] // lanes


def test_2_kv_heads_of_128_in_one_slot_serve_the_references_logits(ref):
    """Qwen3-Next's attention at a toy size with heads of whole vregs: 2 kv
    heads of 128 live in ONE slot of 256 lanes (``pool_page``). Chunks (the
    gather path splits the lanes of the gathered rows) and decode steps (the
    kernel, interpreted, scores heads that share a row) against the
    reference at the model's own 2 heads."""
    hf = dict(HF, head_dim=128, num_hidden_layers=2,
              layer_types=["linear_attention", "full_attention"])
    table = ref.weight_shapes(hf)
    w = weights.make_weights(table, seed=2**31 + 38)
    app = _app(ref, w, hf=hf)
    assert app.cache["k"].shape[2:] == (8, 1, 256)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    first = ad.add_requests([2, 3], [Q45, S12])
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 3)
    for sid, prompt in ((1, R21), (2, Q45), (3, S12)):
        fed = prompt + stream[sid][:-1]
        np.testing.assert_allclose(tap.logits(sid, len(fed)),
                                   _want(ref, w, fed, hf), atol=ATOL)
    kernels = {(k["site"], k["path"]): k["reason"]
               for k in app.warmup_state()["kernels"]}
    assert kernels["paged_decode", "pallas-interpret"] == \
        "pages=16 heads=2 form=mxu-blockdiag fold=2 stored " \
        "prefetch=across-rows"


# ---------------------------------------------------------------------------
# (c) the controls: each must fail (a)'s comparison
# ---------------------------------------------------------------------------

def _bf16_state(monkeypatch):
    shapes = ssm.ssm_state_shapes

    def rounded(*a, **kw):
        out = shapes(*a, **kw)
        return dict(out, ssm=(out["ssm"][0], jnp.bfloat16))
    monkeypatch.setattr(ssm, "ssm_state_shapes", rounded)


def _zero_state_per_chunk(monkeypatch):
    chunked = ssm._delta_chunked

    def forgetful(q, k, v, g, beta, st0, chunk):
        chunk = 16                 # the toy's chunks are shorter than 64
        outs = [chunked(*(a[:, i:i + chunk] for a in (q, k, v, g, beta)),
                        jnp.zeros_like(st0), chunk)
                for i in range(0, q.shape[1], chunk)]
        return jnp.concatenate([o for o, _ in outs], axis=1), outs[-1][1]
    monkeypatch.setattr(ssm, "_delta_chunked", forgetful)


def _no_attention_output_gate(monkeypatch):
    sigmoid = jax.nn.sigmoid
    block = model_base._attn_block

    def ungated(*a, **kw):
        monkeypatch.setattr(jax.nn, "sigmoid", jnp.ones_like)
        try:
            return block(*a, **kw)
        finally:
            monkeypatch.setattr(jax.nn, "sigmoid", sigmoid)
    monkeypatch.setattr(model_base, "_attn_block", ungated)


def _no_shared_expert_gate(monkeypatch):
    shared = moe.shared_experts

    def ungated(spec, x, layer_w):
        return shared(dataclasses.replace(spec, shared_gated=False), x,
                      layer_w)
    monkeypatch.setattr(moe, "shared_experts", ungated)


def _renormalised_over_the_held_only(monkeypatch):
    """The top-k probabilities renormalised over those of them that fell to
    held experts, instead of over all ten."""
    route = moe.route_groups

    def held_only(spec, h, router_w, router_bias=None):
        vals, idx, groups = route(spec, h, router_w, router_bias)
        mine = (idx >= spec.first_expert) & (
            idx < spec.first_expert + spec.held_experts)
        kept = jnp.where(mine, vals, 0.0)
        return (kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-20), idx,
                groups)
    monkeypatch.setattr(moe, "route_groups", held_only)


def _keys_tiled_over_value_heads(monkeypatch):
    """Value head j reads key head j % key_heads (``tile``) instead of
    j // group (``repeat_interleave``)."""
    repeat = jnp.repeat

    def tiled(a, repeats, axis=None, **kw):
        if axis == 2 and a.ndim == 4 and not kw:
            return jnp.concatenate([a] * repeats, axis=2)
        return repeat(a, repeats, axis=axis, **kw)
    mixer = ssm.gated_delta_mixer

    def with_tiled(*a, **kw):
        monkeypatch.setattr(jnp, "repeat", tiled)
        try:
            return mixer(*a, **kw)
        finally:
            monkeypatch.setattr(jnp, "repeat", repeat)
    monkeypatch.setitem(ssm._SSM_BLOCKS, "gated_delta", with_tiled)


def _one_plus_w_read_as_w(monkeypatch):
    _respec(monkeypatch, norm_offset=0.0)


CONTROLS = [_bf16_state, _zero_state_per_chunk, _no_attention_output_gate,
            _no_shared_expert_gate, _renormalised_over_the_held_only,
            _keys_tiled_over_value_heads, _one_plus_w_read_as_w]


@pytest.mark.parametrize("break_it", CONTROLS)
def test_c_a_control_fails_the_comparison(ref, gate_weights, monkeypatch,
                                          break_it):
    break_it(monkeypatch)
    app = _app(ref, gate_weights)
    _, tap, stream = _serve_p69(app, decode=24)
    assert _error(tap, ref, gate_weights, 7, P69, stream) > 10 * ATOL


def test_c_fp8_rounded_reference_weights_fail_the_comparison(ref,
                                                             gate_weights):
    """One precision down: the reference on fp8-rounded weights against
    itself."""
    w8 = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
          for k, v in gate_weights.items()}
    fed = P69 + Q45
    assert np.abs(_want(ref, w8, fed)
                  - _want(ref, gate_weights, fed)).max() > 10 * ATOL


# ---------------------------------------------------------------------------
# (d) the shares of one layer add up to the whole layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tokens, path", [(6, "dense"), (40, "ragged")])
def test_d_four_shares_add_up_to_the_uncut_layer(ref, gate_weights, tokens,
                                                 path):
    """The uncut reference's sparse block (all 16 experts held) against the
    sum of the program's four shares of 4, the gated shared expert (whole on
    every share) counted once."""
    uncut = dict(HF, num_experts=16, router_num_experts=None, first_expert=0)
    table = ref.weight_shapes(uncut)
    w = weights.make_weights(table, seed=2**31 + 37)
    x = jnp.asarray(4 * np.random.default_rng(5).normal(
        size=(2, tokens // 2, 32)), jnp.float32)
    want, _ = ref.sparse_block(uncut, w, 1, x)

    family = get_family("qwen3_next")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **uncut))
    lw = jax.tree.map(lambda a: jnp.asarray(a)[1],
                      family.convert_hf_state_dict(
                          weights.HfView(table, w, dtype=np.dtype("float32")),
                          spec)["layers"])
    whole = dataclasses.replace(spec.moe, dense_max_tokens=16)
    assert moe.takes_ragged(whole, tokens) == (path == "ragged")
    np.testing.assert_allclose(moe.moe_block(whole, x, lw), want, atol=2e-5)
    shared = moe.shared_experts(whole, x, lw)
    assert np.abs(np.asarray(shared)).max() > 1e-3
    total, tallies = 0, []
    for first in (0, 4, 8, 12):
        mine = dataclasses.replace(whole, held_experts=4, first_expert=first)
        lw_mine = dict(lw, **{k: lw[k][first:first + 4] for k in
                              ("expert_gate", "expert_up", "expert_down")})
        total = total + moe.moe_block(mine, x, lw_mine, tally=tallies)
    np.testing.assert_allclose(total - 3 * shared, want, atol=2e-5)
    # every assignment fell to exactly one share
    assert sum(int(t[1]) for t in tallies) == tokens * 4
    assert all(0 < int(t[0]) <= 4 for t in tallies)


# ---------------------------------------------------------------------------
# the loader, the counts, refusals, the warm-up plan
# ---------------------------------------------------------------------------

def test_the_loader_takes_the_interleaved_projections_apart(ref,
                                                            gate_weights):
    """``in_proj_qkvz`` is 2 key-head groups of [q 8 | k 8 | v 2 x 16 | z 2 x
    16], ``in_proj_ba`` of [b 2 | a 2]; ``q_proj`` 4 heads of [query 16 |
    gate 16]: each row of the published tensors lands in its column of the
    program's fused weights, through ``HfView``."""
    table = ref.weight_shapes(HF)
    view = weights.HfView(table, gate_weights, dtype=np.dtype("float32"))
    family = get_family("qwen3_next")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **HF))
    host = family.convert_hf_state_dict(view, spec)
    qkvz = view["model.layers.1.linear_attn.in_proj_qkvz.weight"]
    ba = view["model.layers.1.linear_attn.in_proj_ba.weight"]
    got, got_ab = host["ssm_layers"]["gdn_in"][1], \
        host["ssm_layers"]["gdn_in_ab"][1]
    assert got.shape == (32, 2 * 16 + 2 * 64) and got_ab.shape == (32, 8)
    group = qkvz.reshape(2, 80, 32)
    for h in range(2):
        np.testing.assert_array_equal(got[:, 8 * h:8 * h + 8],
                                      group[h, :8].T)            # q
        np.testing.assert_array_equal(got[:, 16 + 8 * h:24 + 8 * h],
                                      group[h, 8:16].T)          # k
        np.testing.assert_array_equal(got[:, 32 + 32 * h:64 + 32 * h],
                                      group[h, 16:48].T)         # v (2 heads)
        np.testing.assert_array_equal(got[:, 96 + 32 * h:128 + 32 * h],
                                      group[h, 48:80].T)         # z
        np.testing.assert_array_equal(got_ab[:, 2 * h:2 * h + 2],
                                      ba.reshape(2, 4, 32)[h, 2:].T)   # a
        np.testing.assert_array_equal(got_ab[:, 4 + 2 * h:6 + 2 * h],
                                      ba.reshape(2, 4, 32)[h, :2].T)   # b
    q_proj = view["model.layers.3.self_attn.q_proj.weight"].reshape(
        4, 2, 16, 32)
    fused = host["attn_layers"]["qkv_proj"][0]
    assert fused.shape == (32, 64 + 32 + 32 + 64)
    np.testing.assert_array_equal(fused[:, :64],
                                  q_proj[:, 0].reshape(64, 32).T)
    np.testing.assert_array_equal(fused[:, 128:],
                                  q_proj[:, 1].reshape(64, 32).T)
    # the share's experts: the seeded checkpoint holds them alone, at 0..3
    assert host["layers"]["expert_up"].shape == (4, 4, 32, 16)
    assert host["layers"]["router"].shape == (4, 32, 16)
    np.testing.assert_array_equal(
        host["layers"]["expert_up"][2, 3],
        view["model.layers.2.mlp.experts.3.up_proj.weight"].T)
    # ... and a checkpoint with all 16 is read at first_expert + e
    full_hf = dict(HF, num_experts=16, router_num_experts=None,
                   first_expert=0)
    full_table = ref.weight_shapes(full_hf)
    full = weights.HfView(full_table,
                          weights.make_weights(full_table, seed=3),
                          dtype=np.dtype("float32"))
    host = family.convert_hf_state_dict(full, spec)
    np.testing.assert_array_equal(
        host["layers"]["expert_up"][2, 3],
        full["model.layers.2.mlp.experts.7.up_proj.weight"].T)


def test_a_decode_steps_routing_is_counted_on_the_device(ref, gate_weights):
    """``host_stats`` after n decode steps of one live row: the slots are
    held x expert layers x steps, the assignments that fell to held experts
    and the experts they touched are the reference's routing of the same
    tokens (a pad row clones row 0: it touches what row 0 touches; a dead
    row is left out)."""
    reg = telemetry.MetricsRegistry()
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app, telemetry=reg)
    stream = {7: [ad.add_requests([7], [R21])[7]]}
    assert "moe_expert_slots" not in ad.host_stats      # prefill counts none
    _decode(ad, [7], stream, 5)
    fed = R21 + stream[7][:-1]
    ids = jnp.asarray([fed])
    w = gate_weights
    # the reference's routing at the 5 decode positions, layer by layer
    import importlib
    r = importlib.import_module("harness.reference")
    h = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    touched = assigned = 0
    full, lin = [3], [0, 1, 2]
    for i in range(4):
        x = ref.norm1p(h, w[r.L + "input_layernorm.weight"][i], 1e-6)
        mixed = (ref._attention(HF, w, 0, x) if i in full
                 else ref._linear_attention(HF, w, lin.index(i), x)[0])
        h = h + mixed
        x2 = ref.norm1p(h, w[r.L + "post_attention_layernorm.weight"][i],
                        1e-6)
        _, idx, _ = ref.routing(HF, w, i, x2)
        y, _ = ref.sparse_block(HF, w, i, x2)
        h = h + y
        idx = np.asarray(idx)[0, len(R21):]                  # (5, k)
        mine = (idx >= 4) & (idx < 8)
        assigned += int(mine.sum())
        touched += sum(len(set(step[m].tolist()))
                       for step, m in zip(idx, mine))
    st = ad.host_stats
    assert st["moe_expert_slots"] == 4 * 4 * 5
    assert st["moe_assignments_held"] == assigned > 0
    assert st["moe_experts_touched"] == touched > 0
    series = {s["labels"]["count"]: s["value"] for s in
              reg.snapshot()["metrics"][tmetrics.MOE_EXPERTS_TOTAL]["series"]}
    # the toy's experts (32 x 16) are no whole tiles: the dense path, which
    # reads every held expert of every layer
    assert st["moe_experts_read"] == 80 and st["moe_experts_skipped"] == 0
    assert series == {"touched": touched, "slots": 80, "assigned": assigned,
                      "read": 80}


#: the toy with experts of whole 128-lane tiles: what the few-token kernel
#: (ops/moe_decode.py) takes, on the recurrent walk's static layer loop
HF_TILES = dict(HF, hidden_size=128, moe_intermediate_size=128)


def test_the_decode_step_reads_the_touched_experts_of_the_share(ref):
    """ISSUE 37 on the recurrent walk: served with experts of whole tiles,
    a decode step and a one-row chunk of 32 tokens run the kernel on the
    stacked leaves (interpret mode here), the served logits still equal the
    reference's at every position, and the tally's third count says what
    was read: at least what the live row's routing touched (pad rows clone
    row 0), and fewer than the share holds."""
    w = weights.make_weights(ref.weight_shapes(HF_TILES), seed=2**31 + 37)
    app = _app(ref, w, hf=HF_TILES)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = [ad.add_requests([7], [P69])[7]]
    steps = 6
    for _ in range(steps):
        stream.append(ad.step([7])[7])
    fed = P69 + stream[:-1]
    want = np.asarray(ref.forward(HF_TILES, w, jnp.asarray([fed])))[0]
    np.testing.assert_allclose(tap.logits(7, len(fed)), want, atol=ATOL,
                               rtol=1e-4)
    kernels = {(k["site"], k["path"]): k["reason"]
               for k in app.warmup_state()["kernels"]}
    assert kernels[("moe_decode", "pallas-interpret")] == "pieces=1 of 128"
    assert ("moe_decode", "xla") not in kernels
    st = ad.host_stats
    slots = 4 * 4 * steps
    assert st["moe_expert_slots"] == slots
    assert st["moe_experts_read"] + st["moe_experts_skipped"] == slots
    assert 0 < st["moe_experts_touched"] <= st["moe_experts_read"] < slots


@pytest.mark.parametrize("experts", ["walk", "ragged"])
def test_the_walk_counter_follows_the_programs_engagement_record(
        ref, monkeypatch, experts):
    """ISSUE 39: ``host_stats["prefill_dispatches_moe_walk"]`` counts the
    prefill dispatches whose PROGRAM's trace noted the walk over the
    touched experts (``app.paged_program_notes``), beside
    ``prefill_dispatches``; its twin in the registry carries the path as a
    label. With ``dense_max_tokens`` 0 the same three chunks go through the
    grouped matmuls and count under ``ragged`` alone."""
    if experts == "ragged":
        _respec(monkeypatch, moe=dict(dense_max_tokens=0))
    w = weights.make_weights(ref.weight_shapes(HF_TILES), seed=2**31 + 37)
    app = _app(ref, w, hf=HF_TILES)
    reg = telemetry.MetricsRegistry()
    ad = PagedEngineAdapter(app, telemetry=reg)
    assert app.paged_program_notes(1, 32) == frozenset()      # not traced yet
    ad.add_requests([7], [P69])
    st = ad.host_stats
    assert st["prefill_dispatches"] == 3
    assert st["prefill_dispatches_moe_walk"] == (3 if experts == "walk"
                                                 else 0)
    for width in (32, 8):
        notes = app.paged_program_notes(1, width)
        assert kernel_mode.experts_path(notes) == experts
        assert notes <= {(k["site"], k["path"], k["reason"])
                         for k in app.warmup_state()["kernels"]}
    series = {s["labels"]["experts"]: s["value"] for s in reg.snapshot()[
        "metrics"][tmetrics.PREFILL_DISPATCHES_TOTAL]["series"]}
    assert series == {experts: 3}
    # the decode step is no prefill dispatch, whatever its experts took
    ad.step([7])
    assert st["prefill_dispatches"] == 3


#: a router wide enough that a 256-token chunk gives an expert few rows
#: (256 x 4 / 64 = 16): the chunk's kernel, an expert against ITS rows
HF_WIDE = dict(HF_TILES, router_num_experts=64, first_expert=8)


def test_a_256_token_chunk_walks_the_touched_experts(ref):
    """ISSUE 39 end to end: a prompt of 300 tokens goes through the one-row
    program in two chunks of 256 (the second padded), each of which hands
    the kernel its assignments sorted by expert; then decode. The served
    logits equal the reference's at every position, the record names the
    chunk's form, and both dispatches count as the walk."""
    w = weights.make_weights(ref.weight_shapes(HF_WIDE), seed=2**31 + 39)
    app = _app(ref, w, hf=HF_WIDE, seq_len=512, pa_num_blocks=160,
               context_encoding_buckets=[32, 256])
    prompt = np.random.default_rng(39).integers(1, 128, size=300).tolist()
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = [ad.add_requests([7], [prompt])[7]]
    for _ in range(3):
        stream.append(ad.step([7])[7])
    assert tap.shapes[:2] == [(1, 256), (1, 256)]
    fed = prompt + stream[:-1]
    want = np.asarray(ref.forward(HF_WIDE, w, jnp.asarray([fed])))[0]
    np.testing.assert_allclose(tap.logits(7, len(fed)), want, atol=ATOL,
                               rtol=1e-4)
    assert ("moe_decode", "pallas-interpret",
            "pieces=1 of 128 rows=256 by expert in tiles of 128") \
        in app.paged_program_notes(1, 256)
    st = ad.host_stats
    assert st["prefill_dispatches"] == st["prefill_dispatches_moe_walk"] == 2


def _config(serve=None, **hf):
    family = get_family("qwen3_next")
    tcfg = TpuConfig(dtype="float32", **dict(SERVE, **(serve or {})))
    return family, family.config_cls(tcfg, **dict(HF, **hf))


@pytest.mark.parametrize("serve, hf, error, sentence", [
    (dict(tp_degree=2), {}, NotImplementedError, "served on one chip"),
    (dict(ep_degree=2), {}, NotImplementedError, "without the exchange"),
    ({}, dict(decoder_sparse_step=2), NotImplementedError,
     "decoder_sparse_step = 2"),
    ({}, dict(mlp_only_layers=[1]), NotImplementedError, "mlp_only_layers"),
    ({}, dict(attention_bias=True), NotImplementedError, "attention_bias"),
    ({}, dict(linear_num_key_heads=3), ValueError, "not a multiple"),
    ({}, dict(first_expert=13), ValueError, "held of a router over 16"),
    (dict(is_prefix_caching=True), {}, NotImplementedError,
     "prefix caching (" + model_base.RECURRENT_UNSUPPORTED["prefix caching"]),
    (dict(decode_chunk_tokens=4), {}, NotImplementedError,
     "fused decode loop ("
     + model_base.RECURRENT_UNSUPPORTED["fused decode loop"]),
])
def test_the_family_refuses_with_a_sentence(serve, hf, error, sentence):
    family, config = _config(serve, **hf)
    with pytest.raises(error) as ei:
        family.build_spec(config)
    assert sentence in str(ei.value)


def test_the_spec_is_the_published_keys():
    family, config = _config(layer_types=None)
    spec = family.build_spec(config)
    # the pattern from the interval where layer_types is not spelt out
    assert spec.resolved_ssm_pattern == (True, True, True, False)
    assert (spec.norm_position, spec.norm_offset, spec.qk_norm,
            spec.attn_out_gate, spec.rope.rotary_dim,
            spec.tie_word_embeddings) == ("pre", 1.0, True, True, 4, False)
    s, m = spec.ssm, spec.moe
    assert (s.kind, s.num_heads, s.key_heads, s.d_state, s.head_dim,
            s.qkv_size, s.beta_scale) == ("gated_delta", 4, 2, 8, 16,
                                          2 * 16 + 64, 1.0)
    assert (m.num_experts, m.num_held, m.first_expert, m.top_k,
            m.normalize_topk, m.shared_intermediate, m.shared_gated) == \
        (16, 4, 4, 4, True, 16, True)
    params = model_base.decoder_param_specs(spec)
    assert sorted(params["layers"]) == sorted([
        "input_norm", "post_norm", "router", "expert_gate", "expert_up",
        "expert_down", "shared_gate", "shared_up", "shared_down",
        "shared_gate_w"])
    assert params["layers"]["router"].shape == (4, 32, 16)
    assert params["layers"]["expert_down"].shape == (4, 4, 16, 32)
    assert params["attn_layers"]["qkv_proj"].shape == (1, 32, 2 * 64 + 64)
    assert params["attn_layers"]["q_norm"].shape == (1, 16)
    assert ssm.ssm_state_shapes(s, 3, BATCH, jnp.float32)["conv_x"][0] == \
        (3, BATCH, 96, 3)
    # every expert held where the config names no router width
    whole = family.build_spec(_config(router_num_experts=None,
                                      first_expert=0)[1]).moe
    assert (whole.num_experts, whole.held_experts, whole.holds_share) == \
        (4, 0, False)


def test_warmup_plan_and_the_engagement_record(ref, gate_weights):
    app = _app(ref, gate_weights)
    report = precompile(app, widths=[1, 8, 32])
    pairs = [(g["kind"], g["bucket"]) for g in report["graphs"]]
    per_tw = [("paged", 1), ("paged", 8), ("paged_pack", 8), ("paged", 32),
              ("paged_pack", 32)]
    # ... and, last, the program that makes a carried step's ids
    assert pairs == per_tw * len(app._bt_buckets) + [("carry_ids", BATCH)]
    notes = {k["site"]: k for k in report["kernels"]}
    assert notes["moe_share"] == {"site": "moe_share", "path": "xla",
                                  "reason": "held=4 of 16 from 4 top_k=4"}
    slot_bytes = 3 * (4 * 8 * 16 * 4 + 96 * 3 * 4)
    assert notes["recurrent_state"]["reason"].startswith(
        f"kind=gated_delta slot_bytes={slot_bytes} chunk=64: ")
    ad = PagedEngineAdapter(app)
    ad.add_requests([0], [P69])
    ad.add_requests([1, 2], [Q45, S12])
    for _ in range(3):
        ad.step()
    warm = app.warmup_state()
    assert warm["steady_state"] and not warm["incidents"]
