"""Olmo-Hybrid on the paged serving path (ISSUE 34): gated delta-rule
linear attention as a second kind of recurrent state, post-norm blocks on
the recurrent walk.

``olmo_hybrid`` (three delta-rule layers to one full-attention layer, the
architecture of allenai/Olmo-Hybrid-7B) served through
``PagedEngineAdapter`` with default arguments, at a toy size on the CPU in
float32, in ``tests/test_recurrent_paged.py``'s manner: every test holds the
LOGITS of the served path, at every position a dispatch computed, to the
plain reference ``benchmark/references/olmo_hybrid.py`` (token-by-token
recurrence, itself held to ``transformers``' Gated DeltaNet by
``tests/test_gated_delta.py`` and ``benchmark/tests/
test_reference_olmo_hybrid.py``):

  (a) a prompt walked in three chunks through the ONE-ROW program with
      ``state_slots``, a padded last chunk, then decode through the KV pool
      and the state slots;
  (b) two prompts packed as rows of one full-batch dispatch beside a
      decoding row, whose slot is a dead row of the pack: left bit for bit;
  (c) a freed slot is zero for its next sequence: the logits of a fresh
      engine;
  (d) the controls: a bf16-carried state, a chunk started from a zero
      state, a dropped ``beta`` doubling and a gate applied before the norm
      each fail (a)'s comparison;

and the edges: the family's refusals, the warm-up plan (five programs, one
``(kind, bucket)`` pair each, nothing compiled afterwards), what the
engagement record says of the state, the slot counters and metrics.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax.numpy as jnp  # noqa: E402
from harness import build, weights  # noqa: E402

from neuronx_distributed_inference_tpu import telemetry  # noqa: E402
from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.modules import ssm  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import (  # noqa: E402
    memory_ledger, precompile)
from neuronx_distributed_inference_tpu.telemetry import \
    metrics as tmetrics  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

#: one period at a toy size: every key of the published config.json
HF = dict(
    model_type="olmo_hybrid", vocab_size=128, hidden_size=32,
    intermediate_size=64, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=512,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=["linear_attention", "linear_attention", "linear_attention",
                 "full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=96, pa_block_size=8, pa_num_blocks=48,
             context_encoding_buckets=[8, 16], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(34)
#: 37 = 16 + 16 + 5: three chunks, the last one padded to the 8 bucket
P37, Q29, R21, S12 = (RNG.integers(1, 128, size=n).tolist()
                      for n in (37, 29, 21, 12))
#: float32 on both sides, the chunked form against the recurrence: the
#: served logits (|logit| up to ~0.5 at this size) agree to a few 1e-6; the
#: controls of (d) move them by 1e-2 and more
ATOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("olmo_hybrid")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 34)


def _app(ref, w, hf=HF, **serve):
    family = get_family("olmo_hybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


@pytest.fixture()
def app(ref, gate_weights):
    return _app(ref, gate_weights)


def _want(ref, w, tokens, hf=HF):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens])))[0]


def _error(tap, ref, w, sid, prompt, stream):
    """Largest difference of the served logits, at every position of prompt
    + delivered tokens but the last, to the reference's."""
    fed = prompt + stream[:-1]
    return float(np.abs(tap.logits(sid, len(fed))
                        - _want(ref, w, fed)).max())


def _check(tap, ref, w, sid, prompt, stream):
    assert _error(tap, ref, w, sid, prompt, stream) < ATOL
    want = _want(ref, w, prompt + stream[:-1])
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _serve_p37(app, decode=6):
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P37])[7]]}
    _decode(ad, [7], stream, decode)
    return ad, tap, stream[7]


def test_a_three_chunks_with_a_padded_last_one_then_decode(app, ref,
                                                           gate_weights):
    ad, tap, stream = _serve_p37(app)
    # one prompt runs the ONE-ROW chunk program: 16 + 16 + 5 (in the 8
    # bucket), each continuing from the slot the last one wrote
    assert tap.shapes == [(1, 16), (1, 16), (1, 8)] + [(BATCH, 1)] * 6
    _check(tap, ref, gate_weights, 7, P37, stream)
    # the slot holds the reference's state after the last token fed
    got = np.asarray(app.cache["ssm"][:, ad._state_slot[7]])
    want = np.asarray(ref.final_states(
        HF, gate_weights, jnp.asarray([P37 + stream[:-1]])))[:, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert ad.host_stats["state_slots_live"] == 1
    ad.release([7])
    assert ad.host_stats["state_slots_live"] == 0
    assert ad._state_free == list(range(BATCH))


#: the same period with a tile the state-step kernel takes (ISSUE 44):
#: (8, 64) where HF's (8, 16) keeps the XLA step
HF_KERNEL = dict(HF, linear_value_head_dim=64)


def test_a_chunks_then_decode_on_the_state_kernel(ref):
    """Three chunks through the chunked form (the one-row program), then
    nine decode steps on the state-step kernel, in place on the slots: the
    logits at every position and the slot's final state are the float32
    reference's, a dead row's slot (three of the four here) is left as it
    was, and the records say which program took which path."""
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 44)
    app = _app(ref, w, hf=HF_KERNEL)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = [ad.add_requests([7], [P37])[7]]
    slot = ad._state_slot[7]
    others = [r for r in range(BATCH) if r != slot]
    idle = np.asarray(app.cache["ssm"][:, others])
    _decode(ad, [7], {7: stream}, 9)
    assert tap.shapes == [(1, 16), (1, 16), (1, 8)] + [(BATCH, 1)] * 9
    fed = P37 + stream[:-1]
    want = _want(ref, w, fed, HF_KERNEL)
    assert float(np.abs(tap.logits(7, len(fed)) - want).max()) < ATOL
    assert stream == want[len(P37) - 1:].argmax(-1).tolist()
    assert app.cache["ssm"].dtype == jnp.float32
    got = np.asarray(app.cache["ssm"][:, slot])
    want = np.asarray(ref.final_states(
        HF_KERNEL, w, jnp.asarray([fed])))[:, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (np.asarray(app.cache["ssm"][:, others]) == idle).all()
    slot_bytes = 3 * (2 * 8 * 64 * 4 + (2 * 2 * 8 + 2 * 64) * 3 * 4)
    what = f"kind=gated_delta slot_bytes={slot_bytes} chunk=64"
    assert ("recurrent_state", "pallas-interpret",
            f"{what} heads=2 tile=8x64") in app.paged_program_notes(BATCH, 1)
    assert ("recurrent_state", "xla",
            f"{what}: 16 tokens a row: the chunked form, "
            f"{ssm.SOLVE_NOTE}") in \
        app.paged_program_notes(1, 16)


def test_the_contiguous_decode_phase_steps_on_the_kernel_too(ref,
                                                             monkeypatch):
    """``generate()`` on the contiguous cache: the rows of its ``decode``
    phase are the state's slots, so its T = 1 steps take the kernel as the
    paged step does (``_delta_step`` is never reached), and the greedy
    tokens are the reference's."""
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication

    def unreachable(*a, **kw):
        raise AssertionError("the XLA step ran where the kernel should")
    monkeypatch.setattr(ssm, "_delta_step", unreachable)
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 44)
    family = get_family("olmo_hybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", batch_size=2, seq_len=64)
    app = CausalLMApplication(None, family.config_cls(tcfg, **HF_KERNEL),
                              family)
    view = weights.HfView(ref.weight_shapes(HF_KERNEL), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    app.init_cache()
    prompts = [P37[:20], Q29[:20]]
    got = np.asarray(app.generate(np.asarray(prompts, np.int32),
                                  max_new_tokens=6)["generated"])
    for row, prompt in zip(got, prompts):
        seq = list(prompt)
        for _ in range(6):
            seq.append(int(_want(ref, w, seq, HF_KERNEL)[-1].argmax()))
        assert row.tolist() == seq[20:]


@pytest.mark.parametrize("stack, on_kernel", [
    ("delta rule, a tile the kernel takes", True),
    ("delta rule, a tile it declines", False),
    ("mamba-2", False), ("attention only", False)])
def test_the_adapter_counts_decode_steps_on_the_state_kernel(stack,
                                                             on_kernel):
    """``host_stats["dispatches_state_kernel"]`` (ISSUE 44; the numerator
    of ``mixer.state_kernel_share``): every decode dispatch of a program
    whose record says ``recurrent_state`` ran on the kernel, none of any
    other stack's; read from the record, once a program shape."""
    from test_prefill_rows import HF as LLAMA
    from test_recurrent_paged import HF as GRANITE
    name, hf = {"mamba-2": ("granitemoehybrid", GRANITE),
                "attention only": ("llama", LLAMA)}.get(
        stack, ("olmo_hybrid", HF_KERNEL if on_kernel else HF))
    family = get_family(name)
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    app.init_random_weights(5).init_cache()
    ad = PagedEngineAdapter(app)
    assert ad.host_stats["dispatches_state_kernel"] == 0
    ad.add_requests([0, 1], [S12, R21])
    for _ in range(4):
        ad.step()
    assert ad.host_stats["dispatches"] == 4
    assert ad.host_stats["dispatches_state_kernel"] == (4 if on_kernel else 0)
    assert ad._state_kernel_shapes == {(BATCH, 1): on_kernel}


def test_b_two_prompts_packed_beside_a_decoding_row(app, ref, gate_weights):
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    slot1 = ad._state_slot[1]
    before = {k: np.asarray(app.cache[k][:, slot1])
              for k in ("conv_x", "ssm")}
    n0 = len(tap.shapes)
    # packed at the full batch (12 of 12 and 16 of 29 tokens); the rest of
    # sequence 2 then continues from its slot in the one-row program
    first = ad.add_requests([2, 3], [Q29, S12])
    assert tap.shapes[n0:] == [(BATCH, 16), (1, 16)]
    # sequence 1's slot was a dead row of the pack: bit for bit as it was
    for k, was in before.items():
        np.testing.assert_array_equal(np.asarray(app.cache[k][:, slot1]),
                                      was)
        assert np.abs(was).max() > 0
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 4)
    for sid, prompt in ((1, R21), (2, Q29), (3, S12)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])
    # a step of a subset leaves the others' slots as they were
    slot3 = ad._state_slot[3]
    was3 = np.asarray(app.cache["ssm"][:, slot3])
    _decode(ad, [2], stream, 2)
    np.testing.assert_array_equal(np.asarray(app.cache["ssm"][:, slot3]),
                                  was3)
    _decode(ad, None, stream, 1)
    for sid, prompt in ((1, R21), (2, Q29), (3, S12)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


def test_c_a_freed_slot_is_zero_for_its_next_sequence(ref, gate_weights):
    def serve(app, warm_with=None):
        ad = PagedEngineAdapter(app)
        if warm_with is not None:
            ad.add_requests([5], [warm_with])
            for _ in range(3):
                ad.step()
            ad.release([5])
            assert np.abs(np.asarray(app.cache["ssm"][:, 0])).max() > 0
        tap = LogitTap(app)
        stream = {9: [ad.add_requests([9], [Q29])[9]]}
        assert ad._state_slot[9] == 0          # the lowest free slot again
        _decode(ad, [9], stream, 4)
        return tap.logits(9, len(Q29) + 4), stream[9], tap

    used, s_used, tap = serve(_app(ref, gate_weights), warm_with=P37)
    fresh, s_fresh, _ = serve(_app(ref, gate_weights))
    np.testing.assert_array_equal(used, fresh)
    assert s_used == s_fresh
    _check(tap, ref, gate_weights, 9, Q29, s_used)


def test_served_through_the_front_door(app, ref, gate_weights):
    """The whole path of the benchmark: two ``POST /v1/generate`` requests
    at once on a localhost socket through ``ServingFrontend`` ->
    ``ServingEngine`` -> ``PagedEngineAdapter()``; every SSE token is the
    reference's greedy choice and every logit a dispatch computed on the way
    is the reference's."""
    import asyncio
    import json

    from neuronx_distributed_inference_tpu.serving.engine import (
        ServingEngine, ServingFrontend)
    tap = LogitTap(app)
    prompts, n_new = {"a": P37, "b": S12}, 6

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
        fe = ServingFrontend(ServingEngine(PagedEngineAdapter(app)))
        host, port = await fe.start()
        try:
            return await asyncio.gather(*(generate(host, port, p)
                                          for p in prompts.values()))
        finally:
            await fe.stop()

    streams = dict(zip(prompts, asyncio.run(main())))
    assert len(tap.by_seq) == 2
    for name, prompt in prompts.items():
        stream = streams[name]
        fed = prompt + stream[:-1]
        want = _want(ref, gate_weights, fed)
        assert stream == want[len(prompt) - 1:].argmax(-1).tolist()
        # the engine keeps one decode step in flight: the step enqueued
        # behind a request's last token computed one position more, and
        # its token was dropped
        sid, = (s for s, got in tap.by_seq.items()
                if sorted(got) in (list(range(len(fed))),
                                   list(range(len(fed) + 1))))
        got = np.stack([tap.by_seq[sid][p] for p in range(len(fed))])
        np.testing.assert_allclose(got, want, atol=ATOL)
    assert not app.kv_mgr.tables


# ---------------------------------------------------------------------------
# a pool with more head slots than the model has kv heads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads, slots", [
    (1, 1), (8, 8), (16, 16), (17, 32), (30, 32), (32, 32), (40, 48)])
def test_the_pool_rounds_many_heads_up_to_whole_tiles(heads, slots):
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import \
        pool_kv_heads
    assert pool_kv_heads(heads) == slots
    assert pool_kv_heads(heads, tp=4) == heads      # a shard's: not padded


def test_18_heads_in_a_pool_of_32_serve_the_references_logits(ref):
    """Olmo-Hybrid's 30 heads at a toy size: 18 kv heads of 64 live in a
    pool of 32 heads' room (stored two to a 128-lane slot, 16 slots),
    the attention block grows q, k, v zero heads to match and drops them
    from its output. Chunks (the gather path) and decode steps (the kernel,
    interpreted) against the reference at the model's own 18 heads."""
    hf = dict(HF, hidden_size=1152, num_attention_heads=18,
              num_key_value_heads=18, num_hidden_layers=2,
              layer_types=["linear_attention", "full_attention"])
    table = ref.weight_shapes(hf)
    w = weights.make_weights(table, seed=2**31 + 36)
    family = get_family("olmo_hybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **SERVE)
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    app._put_params(family.convert_hf_state_dict(
        weights.HfView(table, w, dtype=np.dtype("float32")), app.spec))
    app.init_cache()
    assert app.cache["k"].shape[3:] == (16, 128)
    assert app.params["attn_layers"]["qkv_proj"].shape == (1, 1152, 3 * 1152)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    first = ad.add_requests([2, 3], [Q29, S12])
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 3)
    for sid, prompt in ((1, R21), (2, Q29), (3, S12)):
        fed = prompt + stream[sid][:-1]
        want = np.asarray(ref.forward(hf, w, jnp.asarray([fed])))[0]
        np.testing.assert_allclose(tap.logits(sid, len(fed)), want,
                                   atol=ATOL)
    kernels = {(k["site"], k["path"]): k["reason"]
               for k in app.warmup_state()["kernels"]}
    assert kernels["paged_decode", "pallas-interpret"] == \
        "pages=8 heads=32 form=mxu-blockdiag fold=2 stored " \
        "prefetch=across-rows"


# ---------------------------------------------------------------------------
# (d) the controls: each must fail (a)'s comparison
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
        outs = [chunked(*(a[:, i:i + chunk] for a in (q, k, v, g, beta)),
                        jnp.zeros_like(st0), chunk)
                for i in range(0, q.shape[1], chunk)]
        return jnp.concatenate([o for o, _ in outs], axis=1), outs[-1][1]
    monkeypatch.setattr(ssm, "_delta_chunked", forgetful)


def _respec(monkeypatch, **ssm_fields):
    """The family's spec with fields of its ``SSMSpec`` replaced."""
    import dataclasses
    family = get_family("olmo_hybrid")
    build_spec = family.build_spec.__func__

    def respec(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        return dataclasses.replace(
            spec, ssm=dataclasses.replace(spec.ssm, **ssm_fields))
    monkeypatch.setattr(family, "build_spec", classmethod(respec))


def _no_beta_doubling(monkeypatch):
    _respec(monkeypatch, beta_scale=1.0)


def _gate_before_the_norm(monkeypatch):
    """``rmsnorm(o * silu(g))`` (Mamba-2's order) instead of ``rmsnorm(o) *
    silu(g)``."""
    _respec(monkeypatch, norm_before_gate=False)


@pytest.mark.parametrize("break_it", [
    _bf16_state, _zero_state_per_chunk, _no_beta_doubling,
    _gate_before_the_norm])
def test_d_a_control_fails_the_comparison(ref, gate_weights, monkeypatch,
                                          break_it):
    break_it(monkeypatch)
    app = _app(ref, gate_weights)
    _, tap, stream = _serve_p37(app, decode=24)
    assert _error(tap, ref, gate_weights, 7, P37, stream) > 10 * ATOL


# ---------------------------------------------------------------------------
# refusals, the warm-up plan, the record, the counters
# ---------------------------------------------------------------------------

def _config(serve=None, **hf):
    family = get_family("olmo_hybrid")
    tcfg = TpuConfig(dtype="float32", **dict(SERVE, **(serve or {})))
    return family, family.config_cls(tcfg, **dict(HF, **hf))


@pytest.mark.parametrize("serve, hf, sentence", [
    (dict(tp_degree=2), {}, "served on one chip"),
    ({}, dict(rope_parameters={"rope_theta": 500000.0}),
     "read as no positional embedding"),
    # key heads shared by groups of value heads are walked since ISSUE 36;
    # what is refused is a count that does not divide the value heads
    ({}, dict(linear_num_value_heads=3), "not a multiple"),
    ({}, dict(attention_bias=True), "the loader reads no bias"),
    (dict(is_prefix_caching=True), {},
     "prefix caching (" + model_base.RECURRENT_UNSUPPORTED["prefix caching"]),
    (dict(decode_chunk_tokens=4), {},
     "fused decode loop ("
     + model_base.RECURRENT_UNSUPPORTED["fused decode loop"]),
])
def test_the_family_refuses_with_a_sentence(serve, hf, sentence):
    family, config = _config(serve, **hf)
    with pytest.raises((NotImplementedError, ValueError)) as ei:
        family.build_spec(config)
    assert sentence in str(ei.value)


def test_the_spec_is_the_published_keys():
    family, config = _config()
    spec = family.build_spec(config)
    assert spec.resolved_ssm_pattern == (True, True, True, False)
    assert (spec.norm_position, spec.sandwich_norm, spec.qk_norm_full,
            spec.no_rope, spec.tie_word_embeddings) == \
        ("post", True, True, True, False)
    s = spec.ssm
    assert (s.kind, s.num_heads, s.d_state, s.head_dim, s.d_conv,
            s.chunk_size, s.beta_scale) == ("gated_delta", 2, 8, 16, 4, 64,
                                            2.0)
    params = model_base.decoder_param_specs(spec)
    # a post-norm stack carries no input norms
    assert sorted(params["layers"]) == ["down_proj", "gate_proj",
                                        "post_attn_norm", "post_ff_norm",
                                        "up_proj"]
    assert {"q_norm", "k_norm"} <= set(params["attn_layers"])
    assert params["ssm_layers"]["gdn_in"].shape == (3, 32, 2 * 16 + 2 * 32)
    with pytest.raises(KeyError, match="unknown model family"):
        get_family("olmo_hybrid_2")


def test_warmup_plan_and_the_engagement_record(app):
    report = precompile(app, widths=[1, 8, 16])
    pairs = [(g["kind"], g["bucket"]) for g in report["graphs"]]
    per_tw = [("paged", 1), ("paged", 8), ("paged_pack", 8), ("paged", 16),
              ("paged_pack", 16)]
    # ... and, last, the program that makes a carried step's ids
    assert pairs == per_tw * len(app._bt_buckets) + [("carry_ids", BATCH)]
    assert len(set(pairs)) == len(per_tw) + 1
    # the record names the state kind, a slot's bytes and the scan chunk
    s = app.spec.ssm
    slot_bytes = 3 * (2 * 8 * 16 * 4 + (2 * 2 * 8 + 2 * 16) * 3 * 4)
    # ... and who steps it: a (8, 16) tile is not the kernel's, so every
    # program says xla, each with its reason (ISSUE 44)
    what = f"kind=gated_delta slot_bytes={slot_bytes} chunk={s.chunk_size}"
    no_tile = ("1 tiles of 8x16 a key head are not whole 8x64 tiles under "
               "4194304 bytes")
    for why in (no_tile,
                f"8 tokens a row: the chunked form, {ssm.SOLVE_NOTE}",
                f"16 tokens a row: the chunked form, {ssm.SOLVE_NOTE}"):
        note = {"site": "recurrent_state", "path": "xla",
                "reason": f"{what}: {why}"}
        assert note in report["kernels"]
        assert note in app.warmup_state()["kernels"]
    # everything the default adapter dispatches is warm: no incident
    ad = PagedEngineAdapter(app)
    ad.add_requests([0], [P37])
    ad.add_requests([1, 2], [Q29, S12])
    for _ in range(3):
        ad.step()
    warm = app.warmup_state()
    assert warm["steady_state"] and not warm["incidents"]
    assert memory_ledger(ad)["state"] == {
        "bytes": slot_bytes * BATCH, "slots": BATCH,
        "slot_bytes": slot_bytes, "live": 3}


def test_slot_counters_and_metrics_count_as_for_any_recurrent_stack(app):
    reg = telemetry.MetricsRegistry()
    ad = PagedEngineAdapter(app, telemetry=reg)
    ad.add_requests([0, 1], [S12, R21])
    ad.preempt(1)
    ad.release([0])
    snap = reg.snapshot()["metrics"]

    def series(name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in snap[name]["series"]}
    assert series(tmetrics.STATE_SLOT_EVENTS_TOTAL) == {
        (("engine", "paged"), ("event", "alloc")): 2,
        (("engine", "paged"), ("event", "preempt")): 1,
        (("engine", "paged"), ("event", "free")): 1}
    assert series(tmetrics.STATE_SLOTS) == {
        (("engine", "paged"), ("state", "live")): 0,
        (("engine", "paged"), ("state", "free")): BATCH}
    assert (ad.host_stats["state_slot_allocs"],
            ad.host_stats["state_slot_frees"],
            ad.host_stats["state_slots_live"]) == (2, 2, 0)
    # the KV pool covers the attention layer only
    assert app.cache["k"].shape[0] == app.spec.num_attn_layers == 1
