"""LongCat-Flash on the paged serving path (ISSUE 40): a layer of two
latent-attention sub-blocks, two dense MLPs and one routed block on a
shortcut; a paged pool of latent rows with its own decode path; identity
experts in the router; one chip's share of the routed experts.

``longcat_flash`` served through ``PagedEngineAdapter`` with default
arguments, at a toy size on the CPU in float32, in
``tests/test_qwen3_next_paged.py``'s manner: every test holds the LOGITS of
the served path, at every position a dispatch computed, to the plain
reference ``benchmark/references/longcat_flash.py`` (expanded attention, no
cache; held to ``transformers``' ``LongcatFlashForCausalLM`` by
``benchmark/tests/test_reference.py``), both holding the SAME share: routed
experts 2..5 of 8, beside 4 identity columns.

  (a) a prompt walked in three chunks through the one-row program (each
      behind the prefix the earlier ones cached), a padded last chunk, then
      decode through the latent pool: on the kernel (interpret mode) and on
      the XLA form, on the dense expert path and on the ragged one;
  (b) prompts packed as rows of one full-batch dispatch beside a decoding
      row, rows admitted and released mid-stream;
  (c) every control of the benchmark's gate fails (a)'s comparison;
  (d) the absorbed decode path (kernel and XLA) and both chunk-behind-prefix
      forms against the expanded reference over contexts of 1, 3 and 9 pages
      with ragged row lengths and a pad row;
  (e) the 32 shares of a layer, the identity term and the dense MLPs counted
      once, add up to the uncut reference's layer;

and the edges: the pool the application allocates, the counts of a decode
step's routing, the family's refusals, the engagement record.
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
from neuronx_distributed_inference_tpu.modules import moe  # noqa: E402
from neuronx_distributed_inference_tpu.modules.block_kv_cache import (  # noqa: E402
    latent_lanes, latent_page)
from neuronx_distributed_inference_tpu.ops import mla_decode  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.telemetry import \
    metrics as tmetrics  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

#: two layers at a toy size: every key of the published config.json, and the
#: share: the weights hold routed experts 2..5 of the 8 the router scores
#: beside its 4 identity columns. The latent is a whole vreg (rank 128), so
#: the decode kernel engages in interpret mode; both LoRA scales differ from 1
HF = dict(
    model_type="longcat_flash", vocab_size=128, hidden_size=64,
    ffn_hidden_size=96, expert_ffn_hidden_size=32, num_layers=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=128,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, attention_bias=False,
    attention_method="MLA", hidden_act="silu", rms_norm_eps=1e-5,
    rope_theta=10000000, max_position_embeddings=512,
    routed_scaling_factor=6, n_routed_experts=4, router_n_routed_experts=8,
    first_expert=2, zero_expert_num=4, zero_expert_type="identity",
    moe_topk=3)
BATCH = 4
#: a pack of 4 rows x 32 is 128 tokens, over ``dense_max_tokens`` (64): the
#: ragged path; a one-row chunk and a decode step run the dense one
SERVE = dict(batch_size=BATCH, seq_len=128, pa_block_size=8, pa_num_blocks=64,
             context_encoding_buckets=[8, 32], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=True)
RNG = np.random.default_rng(40)
#: 69 = 32 + 32 + 5: three chunks, the last one padded to the 8 bucket
P69, Q45, R21, S12 = (RNG.integers(1, 128, size=n).tolist()
                      for n in (69, 45, 21, 12))
#: float32 on both sides: the served logits (|logit| up to ~1) agree with
#: the reference's to a few 1e-6; the controls move them by 1e-2 and more
ATOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("longcat_flash")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 40)


def _app(ref, w, hf=HF, **serve):
    family = get_family("longcat_flash")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


def _want(ref, w, tokens, hf=HF, control=None):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens]),
                                  control=control))[0]


def _check(tap, ref, w, sid, prompt, stream):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed)
    np.testing.assert_allclose(tap.logits(sid, len(fed)), want, atol=ATOL)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _respec(monkeypatch, **fields):
    """The family's spec with fields replaced; a dict value replaces fields
    of the sub-spec of that name (``moe``, ``mla``)."""
    family = get_family("longcat_flash")
    build_spec = family.build_spec.__func__

    def respec(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        return dataclasses.replace(spec, **{
            k: (dataclasses.replace(getattr(spec, k), **v)
                if isinstance(v, dict) else v) for k, v in fields.items()})
    monkeypatch.setattr(family, "build_spec", classmethod(respec))


def _kernels(app):
    return {k["site"]: k for k in app.warmup_state()["kernels"]}


@pytest.fixture(scope="module")
def served_p69(ref, gate_weights):
    """P69 walked in three chunks, then 24 decode steps: the tap and the
    stream, shared by (a)'s first case and every control."""
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    _decode(ad, [7], stream, 24)
    return app, tap, stream[7]


# ---------------------------------------------------------------------------
# (a) chunks behind a cached prefix, then decode over the latent pool
# ---------------------------------------------------------------------------

def test_a_three_chunks_then_decode_on_the_kernel(ref, gate_weights,
                                                  served_p69):
    app, tap, stream = served_p69
    assert tap.shapes == [(1, 32), (1, 32), (1, 8)] + [(BATCH, 1)] * 24
    _check(tap, ref, gate_weights, 7, P69, stream)
    notes = _kernels(app)
    assert notes["mla_decode"]["path"] == "pallas-interpret"
    assert notes["mla_decode"]["reason"] == \
        ("latent lanes=256 heads=4 form=absorbed pages=32 "
         "tiles=tokens-held prefetch=across-rows")
    assert notes["moe_share"]["reason"] == \
        "held=4 of 12 from 2 top_k=3 zero=4"
    # heads of 16 lanes are not the prefill kernel's: the XLA form, and why
    assert notes["mla_prefill"]["path"] == "xla"
    assert "absorbed" in notes["mla_prefill"]["reason"]
    assert notes["mla_prefill"]["reason"].endswith(
        "(a head's nope or value lanes not whole vregs)")


@pytest.mark.parametrize("decode, experts", [("xla", "dense"),
                                             ("kernel", "ragged")])
def test_a_the_xla_decode_form_and_the_ragged_experts(
        ref, gate_weights, monkeypatch, decode, experts):
    fields = {}
    if decode == "xla":
        fields["decode_kernel"] = False
    if experts == "ragged":
        # every dispatch, the decode step too, over the sorted grouped
        # matmuls: picks of absent and of identity experts dropped
        fields["moe"] = dict(dense_max_tokens=0)
    _respec(monkeypatch, **fields)
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    _decode(ad, [7], stream, 6)
    _check(tap, ref, gate_weights, 7, P69, stream[7])
    notes = _kernels(app)
    assert notes["mla_decode"]["path"] == (
        "xla" if decode == "xla" else "pallas-interpret")
    assert ("moe_ragged" in notes) == (experts == "ragged")


def test_a_wide_chunk_expands_the_prefix(ref, gate_weights, monkeypatch):
    """A chunk at least ``MLA_EXPAND_MIN_QUERIES`` wide takes the other form
    behind its prefix: the same logits."""
    monkeypatch.setattr(model_base, "MLA_EXPAND_MIN_QUERIES", 32)
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    _decode(ad, [7], stream, 2)
    _check(tap, ref, gate_weights, 7, P69, stream[7])
    reasons = {k["reason"] for k in app.warmup_state()["kernels"]
               if k["site"] == "mla_prefill"}
    assert any("width=32 prefix=expanded" in r for r in reasons)
    assert any("width=8 prefix=absorbed" in r for r in reasons)
    assert all(r.endswith("nope or value lanes not whole vregs)")
               for r in reasons)


#: the toy model with heads of whole vregs (nope and value lanes 128): what
#: ``ops/mla_prefill.py`` takes (interpret mode)
HF_KERNEL = dict(HF, qk_nope_head_dim=128, v_head_dim=128)


@pytest.mark.parametrize("prefill", ["kernel", "xla"])
def test_a_chunks_on_the_prefill_kernel_or_declined_by_the_switch(
        ref, monkeypatch, prefill):
    """Heads of whole vregs: every sub-block of a 32-wide chunk runs its
    attention on ``mla_prefill_attention`` (the second chunk behind the
    first's cached prefix), the 8-wide last chunk is declined by its width;
    ``decode_kernel=False`` declines them all. The reference's logits either
    way, and ``host_stats`` counts which dispatches the kernel served."""
    if prefill == "xla":
        _respec(monkeypatch, decode_kernel=False)
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 48)
    app = _app(ref, w, HF_KERNEL)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    assert tap.shapes == [(1, 32), (1, 32), (1, 8)]
    _decode(ad, [7], stream, 3)
    fed = P69 + stream[7][:-1]
    want = _want(ref, w, fed, HF_KERNEL)
    np.testing.assert_allclose(tap.logits(7, len(fed)), want, atol=ATOL)
    assert stream[7] == want[len(P69) - 1:].argmax(-1).tolist()
    st = ad.host_stats
    assert (st["prefill_dispatches"], st["prefill_dispatches_attn_kernel"]) \
        == (3, 2 if prefill == "kernel" else 0)
    notes = {(k["path"], k["reason"]) for k in app.warmup_state()["kernels"]
             if k["site"] == "mla_prefill"}
    narrow = ("rows=1 width=8 prefix=absorbed in groups of 512 tokens, own "
              "tokens expanded")
    if prefill == "kernel":
        assert notes == {
            ("pallas-interpret",
             "rows=1 width=32 latent lanes=256 heads=4 form=absorbed "
             "tile=4x32 pages=16 folds and own tokens inside"),
            ("xla", f"{narrow} (8 queries a row are not whole sublanes)")}
    else:
        assert notes == {
            ("xla", "rows=1 width=32 prefix=absorbed in groups of 512 tokens, "
             "own tokens expanded (decode_kernel=False)"),
            ("xla", f"{narrow} (decode_kernel=False)")}


# ---------------------------------------------------------------------------
# (b) packs, admissions and releases mid-stream, prefix reuse
# ---------------------------------------------------------------------------

def test_b_rows_admitted_and_released_mid_stream(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    n0 = len(tap.shapes)
    # packed at the full batch, 4 x 32 = 128 tokens: the ragged path; the
    # rest of sequence 2 in the one-row program, behind its cached prefix
    first = ad.add_requests([2, 3], [Q45, S12])
    assert tap.shapes[n0:] == [(BATCH, 32), (1, 32)]
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 3)
    ad.release([3])
    _check(tap, ref, gate_weights, 3, S12, stream[3])
    free = app.kv_mgr.allocator.num_free
    stream[4] = [ad.add_requests([4], [P69])[4]]
    assert app.kv_mgr.allocator.num_free < free
    _decode(ad, None, stream, 3)
    for sid, prompt in ((1, R21), (2, Q45), (4, P69)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


def test_b_a_cached_prefix_of_latent_blocks_is_reused(ref, gate_weights):
    """Prefix caching is ON: a second prompt that shares P69's first 64
    tokens recomputes only what follows them, over the first one's latent
    blocks, and gets the reference's logits."""
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    ad.add_requests([1], [P69])
    # released, its full blocks stay resident under their content hashes
    # (and the tap files a row under the owner of its first block: one)
    ad.release([1])
    twin = P69[:64] + Q45[:9]
    n0 = len(tap.shapes)
    stream = {2: [ad.add_requests([2], [twin])[2]]}
    assert tap.shapes[n0:] == [(1, 32)]              # 9 tokens, one chunk
    _decode(ad, None, stream, 2)
    fed = twin + stream[2][:-1]
    got = np.stack([tap.by_seq[2][p] for p in range(64, len(fed))])
    np.testing.assert_allclose(got, _want(ref, gate_weights, fed)[64:],
                               atol=ATOL)


# ---------------------------------------------------------------------------
# (c) the controls of the benchmark's gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control", [
    "no_q_scale", "no_kv_scale", "no_identity", "renormalised",
    "no_routed_scaling", "shortcut_early", "rope_halves", "no_select_bias"])
def test_c_a_control_fails_the_comparison(ref, gate_weights, served_p69,
                                          control):
    assert control in ref.CONTROLS
    _, tap, stream = served_p69
    fed = P69 + stream[:-1]
    got = tap.logits(7, len(fed))
    assert np.abs(got - _want(ref, gate_weights, fed)).max() < ATOL
    assert np.abs(got - _want(ref, gate_weights, fed,
                              control=control)).max() > 10 * ATOL


def test_c_fp8_rounded_reference_weights_fail_the_comparison(ref,
                                                             gate_weights):
    """One precision down: the reference on fp8-rounded weights against
    itself."""
    w8 = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
          for k, v in gate_weights.items()}
    fed = P69 + Q45
    assert np.abs(_want(ref, w8, fed)
                  - _want(ref, gate_weights, fed)).max() > 10 * ATOL


def _toy_file():
    """The toy as a configuration file ``scripts/gate40.py`` can build."""
    return dict(
        HF, family="longcat_flash", tp=1, dtype="float32", serve=SERVE,
        adapter={},
        gate=dict(config={"num_layers": 1}, batch=2, prompt_len=24,
                  new_tokens=4, atol=2e-4, rtol=1e-4, min_positions_held=1.0,
                  median_ratio_max=0.5, worst_ratio_max=1.0,
                  excuse_margin_max=0.0))


def test_c_the_builders_chip_check_runs_at_a_toy_size():
    """``scripts/gate40.py`` (what PR 40 ran on the CPU backend and on the
    chip at the published widths) at a toy size: the gate passes, every
    control and the fp8-rounded reference fail it, and the long walk (72
    tokens in chunks of 32 behind their prefix, then decode over 10 pages)
    holds every position."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "gate40", os.path.join(ROOT, "scripts", "gate40.py"))
    gate40 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate40)
    out = gate40.gate_and_controls(_toy_file(), seed=2**31 + 40,
                                   served_precision="highest")
    assert out["sound"]["passed"], out["sound"]
    assert set(out["controls"]) == set(build.load_reference(
        "longcat_flash").CONTROLS) | {"fp8_weights",
                                      "fp8_weights_vs_reference"}
    assert not any(v["passed"] for v in out["controls"].values()), out
    assert {n["site"] for n in out["notes"]} == {"latent_cache", "mla_decode",
                                                 "mla_prefill"}
    walk = gate40.long_walk(_toy_file(), seed=2**31 + 40, tokens=72,
                            new_tokens=8, served_precision="highest")
    assert walk["positions_served"] == 80 and walk["held_share"] == 1.0
    assert walk["blocked_vs_plain_reference"] < 1e-6
    assert walk["worst_ratio"] < 0.5


# ---------------------------------------------------------------------------
# (d) the latent attention forms against expanded heads
# ---------------------------------------------------------------------------

def _latent_case(pages, rng):
    """A pool, a table and ragged rows over ``pages`` pages a row: rows 0-2
    live at different lengths (row 1 ends on a page boundary), row 3 a pad
    row (length 0, table of null blocks)."""
    spec = get_family("longcat_flash").build_spec(
        get_family("longcat_flash").config_cls(
            TpuConfig(tp_degree=1, dtype="float32", **SERVE), **HF))
    m, nh, bs = spec.mla, 4, 8
    lanes = latent_lanes(m.latent_dim)
    lens = np.array([pages * bs - 3, (pages - 1) * bs or 5, 2, 0])
    table = np.zeros((4, pages), np.int32)
    for b in range(3):
        table[b] = 1 + b * pages + rng.permutation(pages)
    pool = np.zeros((3, 1 + 3 * pages, bs, 1, lanes), np.float32)
    pool[..., :m.latent_dim] = rng.normal(
        size=pool.shape[:-1] + (m.latent_dim,))
    pool[:, 0] = 0
    w_kvb = rng.normal(size=(m.kv_lora_rank, nh, 32)) * 0.1
    return spec, jnp.asarray(pool), jnp.asarray(table), lens, \
        jnp.asarray(w_kvb, jnp.float32)


def _expanded(spec, q_nope, q_rot, lat_new, w_kvb, pool, li, table, pos):
    """Plain attention over expanded heads of the rows' prefixes + the
    step's own tokens, row by row."""
    m = spec.mla
    r, nope = m.kv_lora_rank, m.qk_nope_head_dim
    out = []
    for b in range(q_nope.shape[0]):
        start = int(pos[b, 0])
        rows = np.asarray(pool)[li, np.asarray(table)[b]].reshape(
            -1, pool.shape[-1])[:start]
        lat = np.concatenate([rows[:, :m.latent_dim], lat_new[b]])
        heads = np.einsum("sr,rhd->shd", lat[:, :r], np.asarray(w_kvb))
        kpos = np.concatenate([np.arange(start), pos[b]])
        s = (np.einsum("thd,shd->hts", q_nope[b], heads[..., :nope])
             + np.einsum("thd,sd->hts", q_rot[b], lat[:, r:])) * spec.scale
        s = np.where(kpos[None, None, :] <= pos[b][None, :, None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hts,shd->thd", p, heads[..., nope:]))
    return np.stack(out)


@pytest.mark.parametrize("pages", [1, 3, 9])
def test_d_the_latent_forms_agree_with_expanded_heads(pages):
    rng = np.random.default_rng(pages)
    spec, pool, table, lens, w_kvb = _latent_case(pages, rng)
    m = spec.mla
    for t, forms in ((1, ("absorbed", "kernel")),
                     (5, ("absorbed", "expanded"))):
        if t > 1:
            # a chunk continues each row behind its prefix; the pad row's
            # positions start at 0
            lens_t = np.minimum(lens, pages * 8 - t) if pages > 1 else \
                np.array([3, 0, 2, 0])
        else:
            lens_t = lens
        pos = lens_t[:, None] + np.arange(t)[None]
        q_nope = rng.normal(size=(4, t, 4, 16)).astype(np.float32)
        q_rot = rng.normal(size=(4, t, 4, 16)).astype(np.float32)
        lat_new = rng.normal(size=(4, t, m.latent_dim)).astype(np.float32)
        want = _expanded(spec, q_nope, q_rot, lat_new, w_kvb, pool, 1,
                         table, pos)
        for form in forms:
            if form == "kernel":
                got = mla_decode.mla_decode_attention(
                    jnp.asarray(q_nope[:, 0]), jnp.asarray(q_rot[:, 0]),
                    jnp.asarray(lat_new[:, 0]), w_kvb, pool, 1,
                    jnp.asarray(lens_t), table, scale=spec.scale,
                    rank=m.kv_lora_rank, interpret=True)[:, None]
            else:
                got = model_base._mla_attend(
                    spec, jnp.asarray(q_nope), jnp.asarray(q_rot),
                    jnp.asarray(lat_new), w_kvb, pool, 1, table,
                    jnp.asarray(pos), form == "absorbed")
            np.testing.assert_allclose(np.asarray(got), want, atol=2e-5,
                                       err_msg=f"{form} t={t}")


def test_d_the_prefix_walk_follows_the_live_prefix_not_the_table():
    """The loop over the cached prefix runs as many groups as the longest
    live prefix needs: a chunk at the head of its prompt gathers nothing,
    whatever the table's width."""
    rng = np.random.default_rng(0)
    spec, pool, table, _, w_kvb = _latent_case(9, rng)
    wide = jnp.pad(table, ((0, 0), (0, 119)))           # 128 blocks a row
    args = [jnp.asarray(rng.normal(size=s), jnp.float32)
            for s in ((4, 8, 4, 16), (4, 8, 4, 16),
                      (4, 8, spec.mla.latent_dim))]
    seen = []
    real = jax.lax.fori_loop

    def spy(lo, hi, body, init):
        seen.append(hi)
        return real(lo, hi, body, init)
    for start in (0, 8, 600):
        pos = jnp.asarray(start + np.arange(8)[None].repeat(4, 0))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax.lax, "fori_loop", spy)
            model_base._mla_attend(spec, *args, w_kvb, pool, 1, wide, pos,
                                   True)
    # groups of 512 tokens = 64 pages of 8: none, one, two
    assert [int(n) for n in seen] == [0, 1, 2]


# ---------------------------------------------------------------------------
# (e) the shares of one layer add up to the whole layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tokens, path", [(6, "dense"), (40, "ragged")])
def test_e_32_shares_add_up_to_the_uncut_layer(ref, tokens, path):
    """The uncut reference's layer (all 32 routed experts held, 8 identity
    columns) against what 32 shares of one expert each give: every share
    computes both attention sub-blocks, both dense MLPs and the identity
    term alike (in the deployment a token's own chip does), so the sum of
    the shares' outputs less 31 times what a share with NO expert's part
    gives is the whole layer."""
    uncut = dict(HF, num_layers=1, n_routed_experts=32,
                 router_n_routed_experts=None, first_expert=0,
                 zero_expert_num=8, moe_topk=6)
    table = ref.weight_shapes(uncut)
    w = dict(weights.make_weights(table, seed=2**31 + 41))
    # a bias of a probability's own spread (the table's is drawn to pick
    # nearly alone at a toy width: every token the same columns)
    bias = ref.ROUTER + "e_score_correction_bias"
    w[bias] = (w[bias].astype(jnp.float32) * 0.25).astype(w[bias].dtype)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, tokens // 2, 64)), jnp.float32)
    want, _ = ref.layer(uncut, w, 0, x)
    eps = uncut["rms_norm_eps"]
    a0 = x + ref.mla(uncut, w, 0, 0, ref.rms_norm(
        x, w[ref._sub(ref.IN_NORM, 0)][0], eps))
    u = ref.rms_norm(a0, w[ref._sub(ref.POST_NORM, 0)][0], eps)
    whole_moe, _ = ref.moe(uncut, w, 0, u)
    dense_part = want - whole_moe       # attention and dense MLPs: once

    family = get_family("longcat_flash")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **uncut))
    lw = jax.tree.map(lambda a: jnp.asarray(a)[0],
                      family.convert_hf_state_dict(
                          weights.HfView(table, w, dtype=np.dtype("float32")),
                          spec)["moe_layers"])
    whole = dataclasses.replace(spec.moe, dense_max_tokens=16)
    assert whole.num_held == 32 and whole.holds_share    # identity columns
    assert moe.takes_ragged(whole, tokens) == (path == "ragged")
    np.testing.assert_allclose(moe.moe_block(whole, u, lw), whole_moe,
                               atol=2e-5)
    top_vals, top_idx = moe.route(whole, u, lw["router"], lw["router_bias"])
    identity = (moe.zero_expert_weight(whole, top_vals, top_idx)[..., None]
                * u)
    assert np.abs(np.asarray(identity)).max() > 1e-3
    total, tallies = 0, []
    for first in range(32):
        mine = dataclasses.replace(whole, held_experts=1, first_expert=first)
        lw_mine = dict(lw, **{k: lw[k][first:first + 1] for k in
                              ("expert_gate", "expert_up", "expert_down")})
        total = total + moe.moe_block(mine, u, lw_mine, tally=tallies)
    np.testing.assert_allclose(dense_part + total - 31 * identity, want,
                               atol=2e-5)
    # every pick fell to exactly one share's expert or to an identity one
    picks, zero = (int(t) for t in tallies[0][3:5])
    assert picks == tokens * 6 and 0 < zero < picks
    assert sum(int(t[1]) for t in tallies) == picks - zero


# ---------------------------------------------------------------------------
# the pool, the counts, refusals
# ---------------------------------------------------------------------------

def test_the_pool_holds_a_latent_row_a_token(ref, gate_weights):
    app = _app(ref, gate_weights)
    assert latent_page(576) == (1, 640, 0) and latent_lanes(144) == 256
    # 2 layers x 2 sub-blocks; one slot of 256 lanes a token; no V
    assert app.cache["k"].shape == (4, 65, 8, 1, 256)
    assert app.cache["v"].shape == (4, 65, 8, 1, 0)
    assert app.kv_mgr.spec.bytes_per_token == 4 * 256 * 4
    assert app.spec.num_attn_layers == 4 and app.spec.num_moe_layers == 2
    # at the published widths: 1,280 B a token a sub-block in bf16, against
    # 64 heads x (192 + 128) expanded
    assert 64 * (192 + 128) * 2 == 32 * latent_lanes(512 + 64) * 2


def test_deepseek_takes_the_same_pool():
    from neuronx_distributed_inference_tpu.models.deepseek.modeling_deepseek \
        import DeepseekFamily
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    cfg = DeepseekFamily.config_cls(
        tcfg, model_type="deepseek_v3", hidden_size=64, num_attention_heads=4,
        num_hidden_layers=2, vocab_size=128, kv_lora_rank=128,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        q_lora_rank=32, intermediate_size=96, rms_norm_eps=1e-5)
    app = PagedCausalLMApplication(None, cfg, DeepseekFamily)
    app.init_random_weights(0).init_cache()
    assert app.spec.mla.q_scale == app.spec.mla.kv_scale == 1.0
    assert app.cache["k"].shape == (2, 65, 8, 1, 256)
    assert app.cache["v"].shape[-1] == 0
    ids = np.asarray([P69[:30]] * BATCH, np.int32)
    paged = app.generate(ids, max_new_tokens=6)["sequences"]
    # the contiguous application of the same weights (expanded heads)
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    flat = CausalLMApplication(None, DeepseekFamily.config_cls(
        TpuConfig(tp_degree=1, dtype="float32", batch_size=BATCH,
                  seq_len=128), **{k: getattr(cfg, k) for k in (
                      "model_type", "hidden_size", "num_attention_heads",
                      "num_hidden_layers", "vocab_size", "kv_lora_rank",
                      "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                      "q_lora_rank", "intermediate_size", "rms_norm_eps")}),
        DeepseekFamily)
    flat.params = app.params
    flat.init_cache()
    np.testing.assert_array_equal(
        np.asarray(paged), np.asarray(flat.generate(
            ids, max_new_tokens=6)["sequences"]))


def test_a_decode_steps_routing_is_counted_on_the_device(ref, gate_weights):
    """``host_stats`` after n decode steps of one live row: every pick,
    those that fell to identity experts and those that fell to held experts
    are the reference's routing of the same tokens - times the batch, since
    a pad row of an attention stack's decode step clones row 0."""
    reg = telemetry.MetricsRegistry()
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app, telemetry=reg)
    stream = {7: [ad.add_requests([7], [R21])[7]]}
    assert "moe_assignments" not in ad.host_stats       # prefill counts none
    _decode(ad, [7], stream, 5)
    fed = R21 + stream[7][:-1]
    x = gate_weights["model.embed_tokens.weight"][
        jnp.asarray([fed])].astype(jnp.float32)
    held = zero = 0
    for i in range(2):
        a0 = x + ref.mla(HF, gate_weights, i, 0, ref.rms_norm(
            x, gate_weights[ref._sub(ref.IN_NORM, 0)][i], 1e-5))
        u = ref.rms_norm(a0, gate_weights[ref._sub(ref.POST_NORM, 0)][i],
                         1e-5)
        idx = np.asarray(ref.routing(HF, gate_weights, i, u)[1])[0, len(R21):]
        held += int(((idx >= 2) & (idx < 6)).sum())
        zero += int((idx >= 8).sum())
        x, _ = ref.layer(HF, gate_weights, i, x)
    st = ad.host_stats
    held, zero = BATCH * held, BATCH * zero
    assert st["moe_assignments"] == BATCH * 2 * 5 * 3
    assert st["moe_assignments_zero"] == zero > 0
    assert st["moe_assignments_held"] == held > 0
    assert st["moe_expert_slots"] == 4 * 2 * 5
    series = {s["labels"]["kind"]: s["value"] for s in reg.snapshot()[
        "metrics"][tmetrics.MOE_ASSIGNMENTS_TOTAL]["series"]}
    assert series == {"held": held, "zero": zero,
                      "absent": BATCH * 30 - held - zero}


def _config(serve=None, **hf):
    family = get_family("longcat_flash")
    tcfg = TpuConfig(dtype="float32",
                     **{**SERVE, "tp_degree": 1, **(serve or {})})
    return family, family.config_cls(tcfg, **dict(HF, **hf))


@pytest.mark.parametrize("serve, hf, error, sentence", [
    (dict(tp_degree=2), {}, NotImplementedError, "served on one chip"),
    ({}, dict(first_expert=6), ValueError, "held of 8"),
    ({}, dict(zero_expert_type="copy"), NotImplementedError,
     "zero_expert_type"),
    ({}, dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
     NotImplementedError, "rope_scaling"),
])
def test_the_family_refuses_with_a_sentence(serve, hf, error, sentence):
    with pytest.raises(error, match=sentence):
        family, cfg = _config(serve, **hf)
        family.build_spec(cfg)


def test_the_spec_is_the_published_keys():
    family, cfg = _config()
    spec = family.build_spec(cfg)
    assert (spec.num_layers, spec.sub_blocks, spec.num_attn_layers) == \
        (2, 2, 4)
    assert spec.mla == model_base.MLASpec(
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, q_lora_rank=32, q_scale=2 ** 0.5, kv_scale=0.5 ** 0.5)
    assert spec.scale == 32 ** -0.5 and spec.rope_interleaved
    assert spec.rope.head_dim == 16 and spec.rope.rope_theta == 1e7
    m = spec.moe
    assert (m.num_experts, m.num_routed, m.num_held, m.first_expert,
            m.zero_experts, m.top_k) == (12, 8, 4, 2, 4, 3)
    assert not m.normalize_topk and m.routed_scaling == 6.0
    assert m.has_router_bias and m.router_bias_mode == "select"
    specs = model_base.decoder_param_specs(spec)
    assert specs["layers"]["kv_b_proj"].shape == (4, 128, 4 * 32)
    assert specs["layers"]["gate_proj"].shape == (4, 64, 96)
    assert specs["moe_layers"]["router"].shape == (2, 64, 12)
    assert specs["moe_layers"]["expert_gate"].shape == (2, 4, 64, 32)
