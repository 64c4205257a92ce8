"""SmallThinker on the paged serving path (ISSUE 43): three rotary window
layers to one NoPE global layer, a KV pool by layer kind (the window layers'
KV in a ring of pages a batch slot), a router that reads the attention's
input, ReLU-gated experts.

``smallthinker`` served through ``PagedEngineAdapter`` at a toy size on the
CPU in float32, in ``tests/test_longcat_flash_paged.py``'s manner: every test
holds the LOGITS of the served path, at every position a dispatch computed,
to the plain reference ``benchmark/references/smallthinker.py`` (no cache, no
kernel; held to a second writing of the equations by
``benchmark/tests/test_reference_smallthinker.py``).

  (a) a prompt walked in five chunks through the one-row program (each
      behind what the earlier ones cached: the ring wraps three times), then
      decode through both pools, on the kernel (interpret mode) and on the
      gathered form;
  (b) prompts packed as rows of one full-batch dispatch beside a decoding
      row; a released and re-used batch slot; a preempted and resumed row; a
      random schedule, under which no write leaves its row's ring;
  (c) every control of the benchmark's gate fails (a)'s comparison;
  (d) the router reads the attention's input, the experts the post-attention
      norm;
  (e) what a window pool refuses, by name;
  (f) a stack WITHOUT a window lowers to the programs it had.
"""

import collections
import dataclasses
import os
import re
import sys
from functools import partial

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from harness import build, weights  # noqa: E402

from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.modules import moe  # noqa: E402
from neuronx_distributed_inference_tpu.modules.block_kv_cache import (  # noqa: E402
    window_pool_spec, window_ring_pages)
from neuronx_distributed_inference_tpu.resilience.errors import \
    ConfigurationError  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import \
    memory_ledger  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402
import test_recurrent_paged as granite_toy  # noqa: E402

#: two periods at a toy size: every key of the published config.json. Heads
#: of 128 lanes, so the paged decode kernel engages in interpret mode; a
#: window of 16 tokens and pages of 8, so a ring is 7 pages = 56 tokens
HF = dict(
    model_type="smallthinker", model_name="toy", vocab_size=128,
    hidden_size=64, head_dim=128, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=8, moe_ffn_hidden_size=128,
    moe_num_primary_experts=8, moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1500000, rope_scaling=None,
    max_position_embeddings=512, sliding_window_size=16,
    sliding_window_layout=[0, 1, 1, 1] * 2, rope_layout=[0, 1, 1, 1] * 2,
    tie_word_embeddings=False)
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=256, pa_block_size=8,
             pa_num_blocks=128, context_encoding_buckets=[8, 32],
             enable_bucketing=True, is_block_kv_layout=True,
             is_prefix_caching=False)
RING = 7
RNG = np.random.default_rng(43)
#: 150 = 4 x 32 + 22: five chunks, the last padded to the 32 bucket; with 20
#: decode steps 170 tokens go through a ring of 56: it wraps three times
P150, Q45, R21, S12, T70 = (RNG.integers(1, 128, size=n).tolist()
                            for n in (150, 45, 21, 12, 70))
#: float32 on both sides: served and reference logits (|logit| up to ~0.5)
#: agree to a few 1e-7; the weakest control moves them by 8e-3
ATOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("smallthinker")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 43)


def _app(ref, w, hf=HF, **serve):
    family = get_family("smallthinker")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


def _want(ref, w, tokens, control=None):
    return np.asarray(ref.forward(HF, w, jnp.asarray([tokens]),
                                  control=control))[0]


def _check(tap, ref, w, sid, prompt, stream):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed)
    np.testing.assert_allclose(tap.logits(sid, len(fed)), want, atol=ATOL)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _kernels(app):
    return collections.defaultdict(list, {
        site: [k for k in app.warmup_state()["kernels"] if k["site"] == site]
        for site in {k["site"] for k in app.warmup_state()["kernels"]}})


def _respec(monkeypatch, **fields):
    family = get_family("smallthinker")
    build_spec = family.build_spec.__func__

    def respec(cls, config, tp_degree=None):
        return dataclasses.replace(build_spec(cls, config, tp_degree),
                                   **fields)
    monkeypatch.setattr(family, "build_spec", classmethod(respec))


@pytest.fixture(scope="module")
def served_p150(ref, gate_weights):
    """P150 walked in five chunks, then 20 decode steps: the tap and the
    stream, shared by (a)'s first case and every control."""
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P150])[7]]}
    _decode(ad, [7], stream, 20)
    return app, ad, tap, stream[7]


# ---------------------------------------------------------------------------
# the pools the application allocates
# ---------------------------------------------------------------------------

def test_the_cache_is_two_pools_by_layer_kind(ref, gate_weights):
    app = _app(ref, gate_weights)
    spec = app.spec
    assert spec.window_pool and spec.nope_global
    assert spec.layer_pattern == (False, True, True, True) * 2
    assert (spec.num_attn_layers, spec.num_window_layers) == (8, 6)
    m = spec.moe
    assert (m.num_experts, m.top_k, m.act) == (8, 3, "relu")
    assert m.pre_softmax_topk and m.normalize_topk and m.router_pre_attn
    assert window_ring_pages(16, 32, 8) == RING == app.window_ring_pages
    # the global layers' pool: the allocator's blocks; the window layers':
    # a ring a batch slot, no null block; a page's shape is pool_page's
    assert app.cache["k"].shape == (2, 129, 8, 1, 256)
    assert app.cache["k_w"].shape == (6, BATCH * RING, 8, 1, 256)
    assert app.cache["v_w"].shape == app.cache["k_w"].shape
    wspec = window_pool_spec(spec, BATCH, 8, 32)
    assert wspec.shape == app.cache["k_w"].shape
    # a window layer never holds more than window + widest + a page (and
    # the page that bound is rounded up to)
    assert RING * 8 < 16 + 32 + 2 * 8
    assert app.state_slots == BATCH and app.kv_mgr.spec.num_layers == 2
    ad = PagedEngineAdapter(app)
    kv = memory_ledger(ad)["kv"]
    assert kv["pages"] == {"global": 0, "window": 0, "window_ring": RING,
                           "window_slots": BATCH,
                           "window_allocated": RING * BATCH * 6}
    assert kv["window_pool_bytes"] == 2 * app.cache["k_w"].size * 4
    assert "state" not in memory_ledger(ad)
    assert {"live_tokens", "blocks"} <= set(kv)


# ---------------------------------------------------------------------------
# (a) chunks behind what the earlier ones cached, then decode; the ring wraps
# ---------------------------------------------------------------------------

def test_a_five_chunks_then_decode_on_the_kernel(ref, gate_weights,
                                                 served_p150):
    app, ad, tap, stream = served_p150
    assert tap.shapes == [(1, 32)] * 5 + [(BATCH, 1)] * 20
    assert (len(P150) + 20) // (RING * 8) == 3          # the ring's wraps
    _check(tap, ref, gate_weights, 7, P150, stream)
    notes = _kernels(app)
    assert {(k["path"], k["reason"].split(" stored ")[1])
            for k in notes["paged_decode"]} == {
        ("pallas-interpret", "prefetch=across-rows window=0"),
        ("pallas-interpret", f"prefetch=across-rows window=16 ring={RING}")}
    # ... and every chunk on the prefill kernel (ISSUE 49): a global layer
    # over the allocator's table, a window layer over its ring - which the
    # five chunks of 32 (twice the window) wrap before the first decode step
    assert {(k["path"], k["reason"]) for k in notes["paged_prefill"]} == {
        ("pallas-interpret", "rows=1 width=32 pages=16 heads=4 fold=2 "
         "tile=4x32 window=0"),
        ("pallas-interpret", "rows=1 width=32 pages=16 heads=4 fold=2 "
         f"tile=4x32 window=16 ring={RING}")}
    assert ad.host_stats["prefill_dispatches_paged_attn_kernel"] \
        == ad.host_stats["prefill_dispatches"] == 5
    pool = notes["kv_window_pool"][0]["reason"]
    assert "layers global=2 window=6" in pool and "ring_pages=7" in pool
    # the counters of a decode dispatch: pages by kind, tokens in window
    stats = ad.host_stats
    pages = [-(-n // 8) for n in range(150, 170)]
    assert stats["kv_window_pages_held"] == 6 * 20 * RING
    assert stats["kv_window_pages_unwindowed"] == 6 * sum(pages)
    assert stats["kv_tokens_in_window"] == 16
    assert stats["kv_tokens_running"] == 169
    assert memory_ledger(ad)["kv"]["pages"]["window"] == 6 * RING


def test_a_the_gathered_decode_form(ref, gate_weights, monkeypatch):
    """``decode_kernel`` False: the decode step gathers the ring's pages as
    a chunk does, under the same mask."""
    _respec(monkeypatch, decode_kernel=False)
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [T70])[7]]}
    _decode(ad, [7], stream, 12)
    _check(tap, ref, gate_weights, 7, T70, stream[7])
    assert {k["path"] for k in _kernels(app)["paged_decode"]} == {"xla"}
    # the chunks gather too, by name, and the counter stays where it was
    assert {(k["path"], k["reason"])
            for k in _kernels(app)["paged_prefill"]} == {
        ("xla", "rows=1 width=32: decode_kernel=False"),
        ("xla", "rows=1 width=8: decode_kernel=False")}
    assert ad.host_stats["prefill_dispatches"] == 3
    assert ad.host_stats["prefill_dispatches_paged_attn_kernel"] == 0


def test_a_the_harness_gate_runs_the_full_batch_prefill(ref):
    """The path ``correct`` sees: ``generate`` prefills at the full batch
    (row i is slot i) and teacher-forces decode; the twin crosses its
    window."""
    res = build.logit_gate(_toy_file(), seed=2**31 + 43,
                           served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 32 * HF["vocab_size"]


# ---------------------------------------------------------------------------
# (b) packs, a re-used slot, a preempted row, a random schedule
# ---------------------------------------------------------------------------

def test_b_rows_admitted_and_released_and_a_slot_reused(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    n0 = len(tap.shapes)
    # two prompts packed at the full batch, in slot order; the rest of
    # sequence 2 in the one-row program
    first = ad.add_requests([2, 3], [Q45, S12])
    assert tap.shapes[n0:] == [(BATCH, 32), (1, 32)]
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 3)
    slot = ad._state_slot[3]
    ad.release([3])
    _check(tap, ref, gate_weights, 3, S12, stream[3])
    # the freed slot's ring still holds sequence 3's keys: the next row
    # takes the slot and must see none of them
    stream[4] = [ad.add_requests([4], [P150])[4]]
    assert ad._state_slot[4] == slot
    _decode(ad, None, stream, 3)
    for sid, prompt in ((1, R21), (2, Q45), (4, P150)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])
    assert ad.host_stats["state_slots_live"] == 3


def test_b_a_preempted_row_resumes_from_its_tokens(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    stream = {1: [ad.add_requests([1], [T70])[1]],
              2: [ad.add_requests([2], [Q45])[2]]}
    _decode(ad, None, stream, 4)
    rec = ad.preempt(1)
    assert list(rec.tokens) == T70 + stream[1]
    assert 1 not in ad._state_slot
    _decode(ad, None, stream, 2)
    tap = LogitTap(app)
    stream[5] = [ad.add_requests([5], [list(rec.tokens)])[5]]
    _decode(ad, None, stream, 3)
    _check(tap, ref, gate_weights, 5, list(rec.tokens), stream[5])


def test_b_no_write_leaves_its_rows_ring_under_a_random_schedule(
        ref, gate_weights):
    """Admissions, steps and releases drawn at random: every token a
    dispatch writes into the window layers' pool lands in ITS row's slot's
    pages, a pad or dead row writes nothing, and every sequence's logits
    are the reference's."""
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    inner = app._run_paged
    writes = []

    def spy(ids, pos, slots, bt, last, *a, state_slots=None, **kw):
        ring = model_base.window_ring_inputs(
            app.spec, app.cache["k_w"], BATCH, jnp.asarray(pos),
            jnp.asarray(slots), jnp.asarray(bt),
            None if state_slots is None else jnp.asarray(state_slots))
        at = np.asarray(ring["slots"])
        row_slot = (np.arange(at.shape[0]) if state_slots is None
                    else np.asarray(state_slots))
        for r in range(at.shape[0]):
            live = at[r][np.asarray(slots)[r] >= 0]
            assert (at[r][np.asarray(slots)[r] < 0] == -1).all()
            assert ((live // 8) // RING == row_slot[r]).all()
            writes.append(len(live))
        if state_slots is not None:
            kw["state_slots"] = state_slots
        return inner(ids, pos, slots, bt, last, *a, **kw)
    app._run_paged = spy
    tap = LogitTap(app)                   # around the spy
    rng = np.random.default_rng(4343)
    prompts, stream, done = {}, {}, {}
    next_id = 10
    for _ in range(40):
        live = sorted(ad.seqs)
        move = rng.choice(["add", "step", "step", "release"])
        if move == "add" and ad.free_capacity:
            prompts[next_id] = rng.integers(
                1, 128, size=int(rng.integers(5, 90))).tolist()
            stream[next_id] = [ad.add_requests(
                [next_id], [prompts[next_id]])[next_id]]
            next_id += 1
        elif move == "release" and live:
            sid = int(rng.choice(live))
            ad.release([sid])
            done[sid] = stream.pop(sid)
        elif live:
            _decode(ad, None, stream, 1)
    assert len(prompts) >= 5 and sum(writes) > 300
    for sid, s in {**done, **stream}.items():
        _check(tap, ref, gate_weights, sid, prompts[sid], s)


# ---------------------------------------------------------------------------
# (c) the controls of the benchmark's gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control", [
    "no_window", "rope_on_global", "window_plus_one", "router_post_attn",
    "silu_gate", "not_renormalised"])
def test_c_a_control_fails_the_comparison(ref, gate_weights, served_p150,
                                          control):
    assert control in ref.CONTROLS
    _, _, tap, stream = served_p150
    fed = P150 + stream[:-1]
    got = tap.logits(7, len(fed))
    assert np.abs(got - _want(ref, gate_weights, fed)).max() < ATOL
    assert np.abs(got - _want(ref, gate_weights, fed,
                              control=control)).max() > 10 * ATOL


def test_c_fp8_rounded_reference_weights_fail_the_comparison(ref,
                                                             gate_weights):
    w8 = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
          for k, v in gate_weights.items()}
    fed = T70 + Q45
    assert np.abs(_want(ref, w8, fed)
                  - _want(ref, gate_weights, fed)).max() > 10 * ATOL


def _toy_file():
    """The toy as a configuration file ``scripts/gate43.py`` and the
    harness's gate can build: the twin is one period."""
    return dict(
        HF, family="smallthinker", tp=1, dtype="float32", serve=SERVE,
        adapter={"prefill_budget_tokens": 32},
        gate=dict(config={"num_hidden_layers": 4,
                          "sliding_window_layout": [0, 1, 1, 1],
                          "rope_layout": [0, 1, 1, 1],
                          "sliding_window_size": 8},
                  batch=2, prompt_len=24, new_tokens=8, atol=2e-4, rtol=1e-4,
                  min_positions_held=1.0, median_ratio_max=0.5,
                  worst_ratio_max=1.0, excuse_margin_max=0.0))


def test_c_the_builders_chip_check_runs_at_a_toy_size():
    """``scripts/gate43.py`` (what PR 43 ran on the CPU backend and on the
    chip at the published widths) at a toy size: the gate passes, every
    control and the fp8-rounded reference fail it, and the long walk at the
    file's own window (150 tokens in chunks of 32 through the adapter's
    deferral, then decode: the ring wraps) holds every position."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "gate43", os.path.join(ROOT, "scripts", "gate43.py"))
    gate43 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate43)
    out = gate43.gate_and_controls(_toy_file(), seed=2**31 + 43,
                                   served_precision="highest")
    assert out["sound"]["passed"], out["sound"]
    assert set(out["controls"]) == set(build.load_reference(
        "smallthinker").CONTROLS) | {"fp8_weights",
                                     "fp8_weights_vs_reference"}
    assert not any(v["passed"] for v in out["controls"].values()), out
    walk = gate43.long_walk(_toy_file(), seed=2**31 + 43, tokens=150,
                            new_tokens=8, served_precision="highest")
    assert walk["window"] == 16 and walk["ring_wraps"] == 2
    assert walk["positions_served"] == 158 and walk["held_share"] == 1.0
    assert walk["passed"] and walk["positions_over_2"] == 0
    assert walk["blocked_vs_plain_reference"] < 1e-6
    assert walk["worst_ratio"] < 0.5


# ---------------------------------------------------------------------------
# (d) the router reads the attention's input
# ---------------------------------------------------------------------------

def test_d_the_routing_reads_a_and_the_experts_m(monkeypatch):
    spec = moe.MoESpec(num_experts=8, top_k=3, intermediate_size=32,
                       pre_softmax_topk=True, act="relu",
                       router_pre_attn=True)
    rng = np.random.default_rng(0)
    a, m = (jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
            for _ in range(2))
    layer_w = {
        "router": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
        "expert_gate": jnp.asarray(rng.normal(size=(8, 16, 32)), jnp.float32),
        "expert_up": jnp.asarray(rng.normal(size=(8, 16, 32)), jnp.float32),
        "expert_down": jnp.asarray(rng.normal(size=(8, 32, 16)), jnp.float32)}
    seen = {}
    route, experts = moe.route, moe._experts
    route_groups = moe.route_groups

    def spy_route(spec_, h, *rest):
        seen["route"] = h
        return route_groups(spec_, h, *rest)

    def spy_experts(spec_, x, *rest):
        seen["experts"] = x
        return experts(spec_, x, *rest)
    monkeypatch.setattr(moe, "route_groups", spy_route)
    monkeypatch.setattr(moe, "_experts", spy_experts)
    tally = []
    got = moe.moe_block(spec, m, layer_w, tally=tally, router_x=a)
    assert seen["route"] is a and seen["experts"] is m
    vals, idx = route(spec, a, layer_w["router"])
    want = moe.experts_dense(spec, m, vals, idx, layer_w["expert_gate"],
                             layer_w["expert_up"], layer_w["expert_down"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(
        tally[0][:3], moe.share_tally(spec, idx, None, None))
    # the weights of a ReLU-gated expert: relu(gate) * up, no SiLU
    one = moe._glu(spec, jnp.asarray([-1.0, 2.0]), jnp.asarray([3.0, 3.0]))
    np.testing.assert_array_equal(one, [0.0, 6.0])
    # a walk that hands no router_x is refused by name, not mis-routed
    with pytest.raises(ValueError, match="router_pre_attn"):
        moe.moe_block(spec, m, layer_w)
    # without the flag the default is the experts' input, as ever
    plain = dataclasses.replace(spec, router_pre_attn=False)
    moe.moe_block(plain, m, layer_w)
    assert seen["route"] is m


# ---------------------------------------------------------------------------
# (e) what a window pool refuses, by name
# ---------------------------------------------------------------------------

def test_e_refusals_by_name(ref, gate_weights):
    family = get_family("smallthinker")

    def spec_of(hf=HF, **serve):
        tcfg = TpuConfig(tp_degree=serve.pop("tp", 1), dtype="float32",
                         **dict(SERVE, **serve))
        return family.build_spec(family.config_cls(tcfg, **hf))
    with pytest.raises(NotImplementedError, match="prefix caching"):
        spec_of(is_prefix_caching=True)
    with pytest.raises(NotImplementedError, match="fused decode loop"):
        spec_of(decode_chunk_tokens=4)
    with pytest.raises(NotImplementedError, match="rope_layout"):
        spec_of(dict(HF, rope_layout=[1] * 8))
    # the sentence names the mechanism and its reason
    why = model_base.window_pool_refusal(["prefix caching", None,
                                          "speculation"])
    assert "ring is overwritten" in why and "speculation" in why
    assert set(model_base.WINDOW_POOL_UNSUPPORTED) >= {
        "prefix caching", "speculation", "ragged dispatch",
        "multi-token decode", "fused decode loop",
        "host KV spill / handoff", "tensor parallelism"}
    app = _app(ref, gate_weights)
    for kw, name in ((dict(ragged=True), "ragged dispatch"),
                     (dict(speculation=2), "speculation"),
                     (dict(kv_spill_tier=object()),
                      "host KV spill / handoff")):
        with pytest.raises(ConfigurationError, match=name):
            PagedEngineAdapter(app, **kw)
    ad = PagedEngineAdapter(app)
    ad.add_requests([1], [S12])
    with pytest.raises(ConfigurationError, match="step_many"):
        ad.step_many(2)
    # the verify and ragged steps refuse the cache itself
    z = jnp.zeros((BATCH, 2), jnp.int32)
    with pytest.raises(NotImplementedError, match="speculation"):
        model_base.paged_spec_verify(
            app.spec, app.tpu_config, app.params, app.cache, z, z, z,
            jnp.zeros((BATCH, 4), jnp.int32), None, jax.random.PRNGKey(0))
    # off the paged layout the family has no pool to split: the contiguous
    # cache's own per-layer sizes (mixed_kv) serve it
    tcfg = TpuConfig(tp_degree=1, dtype="float32", batch_size=2,
                     seq_len=64)
    contiguous = family.build_spec(family.config_cls(tcfg, **HF))
    assert not contiguous.window_pool and contiguous.mixed_kv


#: a gemma2-shaped toy: window layers FIRST in a period of two, rotary on
#: both kinds, soft-capped scores
GEMMA2 = dict(model_type="gemma2", vocab_size=128, hidden_size=64,
              intermediate_size=96, num_hidden_layers=4,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              sliding_window=16, query_pre_attn_scalar=16,
              attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
              rms_norm_eps=1e-6, rope_theta=10000.0,
              max_position_embeddings=512)


def test_e_only_a_family_that_asks_takes_the_pool():
    """The pool by layer kind is never derived: a gemma-shaped local/global
    stack on the paged layout keeps the one pool whatever its rows, and
    builds with what a window pool would refuse (no family but smallthinker
    has run the walk by kind on a chip)."""
    family = get_family("gemma2")

    def pool(**serve):
        tcfg = TpuConfig(tp_degree=1, dtype="float32",
                         **dict(SERVE, **serve))
        return family.build_spec(
            family.config_cls(tcfg, **GEMMA2)).window_pool
    assert not pool()
    assert not pool(is_prefix_caching=True)
    assert not pool(seq_len=64)


@pytest.mark.parametrize("pattern, period", [
    ((False, True, True, True) * 2, 4), ((True, False) * 3, 2),
    ((False, True, True, True), 4), ((True,) * 5 + (False,), 6),
    ((True, True, False) * 2 + (True, True), 3),
    ((True, False, False, True), 3)])
def test_e_the_period_of_a_pattern(pattern, period):
    assert model_base._pattern_period(pattern) == period


def test_e_a_period_that_does_not_divide_the_depth_is_refused():
    """Seven layers of [0,1,1,1]: the walk by kind scans whole periods, so
    the family's spec is refused by name, not unrolled over the stack."""
    family = get_family("smallthinker")
    layout = [0, 1, 1, 1, 0, 1, 1]
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    with pytest.raises(NotImplementedError, match="does not divide"):
        family.build_spec(family.config_cls(tcfg, **dict(
            HF, num_hidden_layers=7, sliding_window_layout=layout,
            rope_layout=layout)))


def test_e_the_walk_by_kind_serves_the_logits_of_the_one_pool():
    """A gemma-shaped stack (window layers FIRST in a period of two, rotary
    on both kinds) served from the pool by layer kind - switched on by hand:
    no family of that shape asks for it - and from one pool for every layer
    (what it is served from): chunks, a second row admitted beside a
    decoding one, the ring wrapping three times; every position's logits
    agree."""
    family = get_family("gemma2")

    def served(pool):
        tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                         **SERVE)
        app = PagedCausalLMApplication(
            None, family.config_cls(tcfg, **GEMMA2), family)
        assert not app.spec.window_pool
        assert app.spec.layer_pattern == (True, False) * 2
        app.spec = dataclasses.replace(app.spec, window_pool=pool)
        app.init_random_weights(seed=3).init_cache()
        assert ("k_w" in app.cache) == pool
        ad = PagedEngineAdapter(app)
        tap = LogitTap(app)
        stream = {7: [ad.add_requests([7], [P150])[7]]}
        _decode(ad, [7], stream, 5)
        stream[8] = [ad.add_requests([8], [Q45])[8]]
        _decode(ad, None, stream, 15)
        return tap.logits(7, 170), tap.logits(8, 60), stream
    by_kind, one_pool = served(True), served(False)
    assert by_kind[2] == one_pool[2]
    for got, want in zip(by_kind[:2], one_pool[:2]):
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# (f) a stack without a window lowers to the programs it had
# ---------------------------------------------------------------------------

#: ops by kind of the lowered ``paged.w1`` (StableHLO, locations stripped)
#: of the harness's toy OLMoE and of ``tests/test_recurrent_paged.py``'s toy
#: granite-4.0-h, as the PARENT of PR 43 lowers them (counted on commit
#: 8aa197e with the function below). Granite's was counted again on PR 45's
#: tree: a T = 1 step's two conv tails a Mamba layer slide by static slices
#: there (``ssm._next_tail``) where ``_conv_tail`` gathered (fewer gathers,
#: adds, selects, constants and broadcasts; nothing else moved, and the
#: toy's ``(16, 16)`` tile keeps the XLA state step). PR 47 gave the routing
#: tally a sixth count, the live rows whose groups reach this chip
#: (``moe.group_tally``; every live row for a router without groups): two
#: constants, a broadcast and a reshape more in OLMoE's step
PARENT_CENSUS = {
    "olmoe": {"chlo.top_k": 1, "func.call": 1, "func.func": 9,
              "stablehlo.add": 34, "stablehlo.broadcast_in_dim": 162,
              "stablehlo.constant": 126, "stablehlo.dot_general": 10,
              "stablehlo.dynamic_slice": 11,
              "stablehlo.dynamic_update_slice": 1, "stablehlo.gather": 5,
              "stablehlo.multiply": 31, "stablehlo.reshape": 33,
              "stablehlo.scatter": 4, "stablehlo.select": 20,
              "stablehlo.while": 1},
    "granite": {"func.func": 18, "stablehlo.add": 79,
                "stablehlo.broadcast_in_dim": 333,
                "stablehlo.constant": 156, "stablehlo.dot_general": 35,
                "stablehlo.gather": 10, "stablehlo.multiply": 105,
                "stablehlo.reshape": 161, "stablehlo.scatter": 11,
                "stablehlo.select": 22},
}


def _census(app):
    app.init_random_weights(seed=0).init_cache()
    b, i32 = app.tpu_config.batch_size, np.int32
    args = (np.zeros((b, 1), i32), np.zeros((b, 1), i32),
            np.full((b, 1), -1, i32), np.zeros((b, app.max_blocks), i32),
            np.zeros((b,), i32), None, jax.random.PRNGKey(0))
    with app._mesh_ctx():
        text = jax.jit(partial(model_base.paged_forward_step, app.spec,
                               app.tpu_config)).lower(
            app.params, app.cache, *args).as_text()
    ops = collections.Counter(re.findall(
        r"= \"?((?:stablehlo|func|chlo)\.[\w.]+)", text))
    ops += collections.Counter(re.findall(
        r"^\s*((?:stablehlo|func)\.[\w.]+)", text, re.M))
    return ops


@pytest.mark.parametrize("name", ["olmoe", "granite"])
def test_f_a_stack_without_a_window_lowers_as_before(name, monkeypatch):
    if name == "olmoe":
        monkeypatch.setattr(build, "DATA_ROOT", os.path.join(
            ROOT, "benchmark", "tests", "toy"))
        app = build.build_app(build.load_json("configs", "toy-olmoe.json"))
    else:
        family = get_family("granitemoehybrid")
        tcfg = TpuConfig(tp_degree=1, dtype="float32", **granite_toy.SERVE)
        app = PagedCausalLMApplication(
            None, family.config_cls(tcfg, **granite_toy.HF), family)
    assert not app.spec.window_pool
    got = _census(app)
    assert {k: got[k] for k in PARENT_CENSUS[name]} == PARENT_CENSUS[name]
    app.init_cache()
    assert set(app.cache) & {"k_w", "v_w"} == set()
