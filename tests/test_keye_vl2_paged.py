"""Keye-VL-2.0's language model on the paged serving path (ISSUE 50): a
learned sparse selection (an indexer over a THIRD paged pool of index keys,
the top ``topk`` tokens a query) in front of GQA, over one chip's share of
the experts.

``keye_vl2`` served through ``PagedEngineAdapter`` at a toy size on the CPU
in float32, in ``tests/test_smallthinker_paged.py``'s manner: every test
holds the LOGITS of the served path, at every position a dispatch computed,
to the plain reference ``benchmark/references/KeyeVL2.py`` (no cache, no
kernel, an explicit top-k a query; held to a second writing of the equations
by ``benchmark/tests/test_reference_KeyeVL2.py``).

  (a) a prompt walked in chunks through the one-row program (each behind
      what the earlier ones cached), then decode through the three pools,
      with ``topk`` below the sequence length and sequences over several
      pages: on both kernels (interpret mode) with the selection as one more
      mask, and on the gathered form;
  (b) prompts packed as rows of one full-batch dispatch beside a decoding
      row; a released block's index keys are not read by its next owner; a
      prefix hit serves the cached index keys;
  (c) with ``topk`` at or above the length the logits are ``qwen3_moe``'s on
      the same weights; the exact selection (``topk_select``) against
      ``jax.lax.top_k`` with ties; the program's chosen set against the
      reference's; the index-key pool's writes;
  (d) the eight shares' routed parts add up to the uncut layer;
  (e) what a selection refuses, by name;
  (f) counters, the ledger and the builder's chip check at a toy size.
"""

import collections
import importlib.util
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

from neuronx_distributed_inference_tpu.config import (  # noqa: E402
    SpeculationConfig, TpuConfig)
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import (  # noqa: E402
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.modules import \
    block_kv_cache as bkv  # noqa: E402
from neuronx_distributed_inference_tpu.resilience.errors import (  # noqa: E402
    ConfigurationError, HandoffError)
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import \
    memory_ledger  # noqa: E402
from neuronx_distributed_inference_tpu import telemetry  # noqa: E402
from neuronx_distributed_inference_tpu.ops import kernel_mode  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

SA = {"indexer_head_dim": 64, "indexer_num_heads": 4,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
      "topk": 16}
#: every key of the published config.json at a toy size. Heads of 128 lanes,
#: so both paged kernels engage in interpret mode; topk 16 and pages of 8,
#: so a query of a 75-token prompt selects a fifth of ten pages
HF = dict(
    model_type="KeyeVL2", vocab_size=128, hidden_size=64, head_dim=128,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
    intermediate_size=128, moe_intermediate_size=128, num_experts=8,
    num_local_experts=8, num_experts_per_tok=3, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=10000000,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    max_position_embeddings=512, max_window_layers=2, sliding_window=None,
    use_sliding_window=False, tie_word_embeddings=False, hidden_act="silu",
    attention_bias=False, decoder_sparse_step=1, mlp_only_layers=[],
    sa_config=SA)
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=256, pa_block_size=8,
             pa_num_blocks=128, context_encoding_buckets=[16, 32],
             enable_bucketing=True, is_block_kv_layout=True,
             is_prefix_caching=True)
RNG = np.random.default_rng(50)
P75, Q45, R21, S13 = (RNG.integers(1, 128, size=n).tolist()
                      for n in (75, 45, 21, 13))
#: float32 on both sides: served and reference logits agree to a few 1e-7;
#: the weakest control moves them by over 1e-2
ATOL = 1e-4


def _hf(**sa):
    return dict(HF, sa_config=dict(SA, **sa))


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("KeyeVL2")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 50)


def _app(ref, w, hf=HF, family="keye_vl2", tcfg_kw=None, **serve):
    family = get_family(family)
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve), **(tcfg_kw or {}))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


def _want(ref, w, tokens, hf=HF, control=None):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens]),
                                  control=control))[0]


def _check(tap, ref, w, sid, prompt, stream, hf=HF, first=0):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed, hf)
    got = tap.by_seq[sid]
    assert sorted(got) == list(range(first, len(fed))), sorted(got)
    np.testing.assert_allclose(
        np.stack([got[p] for p in range(first, len(fed))]), want[first:],
        atol=ATOL)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _notes(app):
    out = collections.defaultdict(list)
    for k in app.warmup_state()["kernels"]:
        out[k["site"]].append((k["path"], k["reason"]))
    return out


# ---------------------------------------------------------------------------
# the pools the application allocates
# ---------------------------------------------------------------------------

def test_the_cache_is_three_pools_on_one_block_table(ref, gate_weights):
    app = _app(ref, gate_weights)
    sp = app.spec.sparse
    assert (sp.index_heads, sp.index_dim, sp.topk) == (4, 64, 16)
    assert sp.rope.head_dim == 64 and sp.rope.rope_theta == 1e7
    assert sp.proj_width == 4 * 64 + 64 + 4
    assert app.spec.qk_norm and app.spec.moe.normalize_topk
    # two tokens of 64 values share a 128-lane row: a page of 8 tokens is
    # (4, 128), and pool_page still decides the K / V page
    assert bkv.index_page(64, 8) == (4, 128)
    assert bkv.index_page(64, 32) == (16, 128)
    assert bkv.index_page(128, 32) == (32, 128)
    assert bkv.index_page(96, 32) == (32, 96)
    assert app.cache["k"].shape == app.cache["v"].shape == \
        (2, 129, 8, 1, 256)
    assert app.cache["k_idx"].shape == (2, 129, 4, 128) == \
        bkv.index_pool_shape(app.spec, 128, 8)
    assert app.state_slots == 0
    layers = app.params["layers"]
    assert layers["idx_proj"].shape == (2, 64, sp.proj_width)
    assert layers["idx_k_norm"].shape == layers["idx_k_norm_b"].shape \
        == (2, 64)
    kv = memory_ledger(PagedEngineAdapter(app))["kv"]
    assert kv["index_pool_bytes"] == app.cache["k_idx"].size * 4
    assert kv["index_block_bytes"] == 2 * 8 * 64 * 4
    assert kv["pool_bytes"] == 2 * app.cache["k"].size * 4


# ---------------------------------------------------------------------------
# (a) chunks behind what the earlier ones cached, then decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["masked-kernels", "masked-gathered"])
def test_a_three_chunks_then_decode_select_as_the_reference_does(
        ref, gate_weights, form):
    app = _app(ref, gate_weights, HF, tcfg_kw=dict(
        attn_block_tkg_kernel_enabled=False)
        if form == "masked-gathered" else None)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P75])[7]]}
    _decode(ad, [7], stream, 12)
    assert tap.shapes == [(1, 32), (1, 32), (1, 16)] + [(BATCH, 1)] * 12
    assert len(P75) + 12 > 10 * 8 > 4 * SA["topk"]
    _check(tap, ref, gate_weights, 7, P75, stream[7])
    # the selection is inside the comparison: dense attention, a halved
    # topk and every other control of the gate move these logits
    fed = P75 + stream[7][:-1]
    want = _want(ref, gate_weights, fed)
    for control in ref.CONTROLS:
        moved = np.abs(_want(ref, gate_weights, fed, control=control)
                       - want).max()
        assert moved > (1e-3 if control in ("keys_fp8", "scores_bf16")
                        else 100 * ATOL), (control, moved)
    notes = _notes(app)
    sparse = dict((why.split(":")[0], path) for path, why in
                  notes["sparse_attn"])
    kernel = "xla" if form == "masked-gathered" else "pallas-interpret"
    assert sparse == {"masked": kernel}
    assert [p for p, _ in notes["paged_decode"]] == [kernel]
    assert {p for p, _ in notes["paged_prefill"]} == {kernel}
    assert notes["kv_index_pool"][0][1].startswith(
        "page=4x128 values_a_token=64 heads=4 topk=16")
    # the selection's own record, a program each: the chunks score and
    # search on the kernel, the step keeps the gathered form by the clock
    # (``index_select.declined``); with the kernels switched off every
    # program says so, and the streams above are the same tokens
    select = {why.split(":")[0]: path
              for path, why in notes["index_select"]}
    off = form == "masked-gathered"
    assert select == {
        f"rows={BATCH} width=1": "xla",
        **({"rows=1 width=32": "xla", "rows=1 width=16": "xla"} if off else {
            "rows=1 width=32 pages=16 heads=4x64 fold=2 topk=16 tile=32x16":
                kernel,
            "rows=1 width=16 pages=16 heads=4x64 fold=2 topk=16 tile=16x16":
                kernel})}
    assert all(("decode_kernel=False" in why) == off
               for _, why in notes["index_select"] if "width=1:" in why)
    # ... and what the adapter counts from it: every dispatch, and those
    # whose program's record names the kernel
    st = ad.host_stats
    assert st["sparse_dispatches"] == st["dispatches"] \
        + st["prefill_dispatches"] == 12 + 3
    assert st["sparse_dispatches_select_kernel"] == (0 if off else 3)
    for shape in [(1, 32), (1, 16), (BATCH, 1)]:
        assert kernel_mode.select_on_kernel(
            app.paged_program_notes(*shape)) is (not off and shape[1] > 1)


def test_a_toy_of_narrow_heads_gathers_the_table_with_the_selection(
        ref, gate_weights):
    """Heads of 16 lanes: both kernels decline and say so, and the gathered
    form masks by the selection."""
    hf = dict(HF, head_dim=16)
    w = weights.make_weights(ref.weight_shapes(hf), seed=2**31 + 51)
    app = _app(ref, w, hf)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [Q45])[1]]}
    _decode(ad, [1], stream, 6)
    _check(tap, ref, w, 1, Q45, stream[1], hf)
    assert all(path == "xla" and "the table gathered" in why
               for path, why in _notes(app)["sparse_attn"])


# ---------------------------------------------------------------------------
# (b) packs, released blocks, a prefix hit
# ---------------------------------------------------------------------------

def test_b_rows_packed_beside_a_decoding_row_and_a_block_reused(
        ref, gate_weights):
    app = _app(ref, gate_weights, pa_num_blocks=24)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    n0 = len(tap.shapes)
    first = ad.add_requests([2, 3], [Q45, S13])
    assert tap.shapes[n0] == (BATCH, 32)          # the pack, full batch
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 3)
    held = set(app.kv_mgr.tables[2])
    ad.release([2])
    _check(tap, ref, gate_weights, 2, Q45, stream[2])
    # the freed blocks still hold sequence 2's index keys (and K / V): the
    # next owner takes them and must read none of them. 24 blocks: the
    # 10 pages of P75 cannot avoid them
    stream[4] = [ad.add_requests([4], [P75])[4]]
    assert held & set(app.kv_mgr.tables[4])
    _decode(ad, None, stream, 3)
    for sid, prompt in ((1, R21), (3, S13), (4, P75)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


def test_b_a_prefix_hit_serves_the_cached_index_keys(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [P75])[1]]}
    _decode(ad, [1], stream, 2)
    # the same prompt again: its whole blocks are found where K and V are,
    # and the suffix's queries select among index keys they did not write
    cached, _ = app.kv_mgr.probe_cached_tokens(P75)
    assert cached == 72
    stream[2] = [ad.add_requests([2], [P75])[2]]
    _decode(ad, [2], stream, 2)
    assert min(tap.by_seq[2]) == cached
    _check(tap, ref, gate_weights, 1, P75, stream[1])
    _check(tap, ref, gate_weights, 2, P75, stream[2], first=cached)
    assert stream[1][:3] == stream[2]
    np.testing.assert_allclose(tap.by_seq[2][74], tap.by_seq[1][74],
                               atol=1e-6)


# ---------------------------------------------------------------------------
# (c) the tie to qwen3_moe, the exact selection, the index-key pool
# ---------------------------------------------------------------------------

def test_c_with_topk_over_the_length_the_logits_are_qwen3_moes(
        ref, gate_weights):
    hf = _hf(topk=512)
    streams, logits = {}, {}
    for family in ("keye_vl2", "qwen3_moe"):
        app = _app(ref, gate_weights, hf, family=family)
        assert (app.spec.sparse is None) == (family == "qwen3_moe")
        assert ("k_idx" in app.cache) == (family == "keye_vl2")
        ad = PagedEngineAdapter(app)
        tap = LogitTap(app)
        stream = {1: [ad.add_requests([1], [Q45])[1]]}
        _decode(ad, [1], stream, 5)
        streams[family] = stream[1]
        logits[family] = tap.logits(1, len(Q45) + 5)
    assert streams["keye_vl2"] == streams["qwen3_moe"]
    np.testing.assert_allclose(logits["keye_vl2"], logits["qwen3_moe"],
                               atol=1e-6)
    np.testing.assert_allclose(
        logits["keye_vl2"],
        _want(ref, gate_weights, Q45 + streams["keye_vl2"][:-1], hf),
        atol=ATOL)


@pytest.mark.parametrize("k", [1, 5, 16, 64])
def test_c_the_exact_selection_breaks_ties_as_top_k_does(k):
    """Scores with many exact ties (a few distinct values, zeros of both
    signs, -inf), rows with fewer valid entries than ``k``: the selected set
    is ``jax.lax.top_k``'s, position for position."""
    rng = np.random.default_rng(k)
    scores = rng.choice(
        np.array([-3.5, -1.0, -0.0, 0.0, 0.25, 0.25, 2.0, 7.5, -np.inf,
                  1e-30, -1e-30], np.float32), size=(3, 7, 48))
    scores[0, 0] = rng.normal(size=48).astype(np.float32)     # no ties
    valid = np.arange(48)[None, None, :] <= rng.integers(
        0, 48, size=(3, 7, 1))
    got = np.asarray(model_base.topk_select(
        jnp.asarray(scores), jnp.asarray(valid), k))
    masked = np.where(valid, np.where(scores == 0, 0.0, scores), -np.inf)
    want = np.zeros_like(valid)
    _, idx = jax.lax.top_k(jnp.asarray(masked), min(k, 48))
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    # a valid -inf score still outranks an invalid entry
    want &= valid
    n_valid = valid.sum(-1)
    assert (got.sum(-1) == np.minimum(n_valid, k)).all()
    finite = np.isfinite(masked).sum(-1) >= np.minimum(n_valid, k)
    assert (got == want)[finite].all()
    assert not (got & ~valid).any()


def test_c_the_programs_chosen_set_is_the_references(ref, gate_weights):
    """``_indexer_block`` over a pool it fills in two chunks and single
    tokens, against the reference's full pass: the same float32 scores to
    rounding and, wherever the reference's margin is no near-tie, the same
    set."""
    from harness.reference import rms_norm
    app = _app(ref, gate_weights)
    spec, n = app.spec, len(P75)
    layer_w = jax.tree.map(lambda a: a[0], app.params["layers"])
    x = gate_weights["model.embed_tokens.weight"][jnp.asarray([P75])]
    a = rms_norm(x.astype(jnp.float32),
                 gate_weights["model.layers.{i}.input_layernorm.weight"][0],
                 HF["rms_norm_eps"])
    scores = ref.index_scores(HF, gate_weights, 0, a)
    kept, margin = ref.select(HF, scores)
    pool = app.cache["k_idx"]
    table = jnp.arange(1, 11, dtype=jnp.int32)[None, :]       # 10 pages
    got = []
    for lo, hi in [(0, 40), (40, 72)] + [(t, t + 1) for t in range(72, n)]:
        pos = jnp.arange(lo, hi, dtype=jnp.int32)[None, :]
        ai = dict(zip(("cos_i", "sin_i"),
                      model_base.rope_cos_sin(pos, spec.sparse.rope)))
        sel, pool = model_base._indexer_block(
            spec, a[:, lo:hi], layer_w, pool, 0, ai, pos, pos + 8, table)
        got.append(np.asarray(sel)[0, :, :n])
    got = np.concatenate(got)
    clear = np.asarray(margin)[0] > 1e-4
    assert clear.sum() > 50
    np.testing.assert_array_equal(got[clear], np.asarray(kept)[0][clear])
    assert (got.sum(-1) == np.minimum(np.arange(n) + 1, SA["topk"])).all()


def test_c_index_keys_are_written_as_whole_rows():
    """Two tokens share a row of the index-key pool: a chunk that starts
    and ends inside rows, a dropped token, single tokens on both halves and
    a rewrite all leave the pool as a plain token-by-token model has it."""
    rng = np.random.default_rng(3)
    bs, dim, n_blocks = 8, 64, 6
    rows, lanes = bkv.index_page(dim, bs)
    pool = jnp.zeros((2, n_blocks, rows, lanes), jnp.float32)
    model = np.zeros((2, n_blocks * bs, dim), np.float32)

    def write(layer, positions, slots):
        nonlocal pool
        new = rng.normal(size=(len(positions), len(positions[0]), dim)
                         ).astype(np.float32)
        pool = bkv.write_index_keys(
            pool, jnp.asarray(new), layer, jnp.asarray(slots, jnp.int32),
            jnp.asarray(positions, jnp.int32), bs)
        for r, row in enumerate(slots):
            for t, slot in enumerate(row):
                if slot >= 0:
                    model[layer, slot] = new[r, t]
    # a chunk of 13 from position 3 (block 2 then block 4), one pad token
    pos = list(range(3, 16)) + [0]
    write(1, [pos], [[2 * bs + p if p < 8 else 4 * bs + p - 8
                      for p in pos[:-1]] + [-1]])
    # two rows of single tokens: offsets 1 (first half) and 6 (second half)
    write(1, [[17], [6]], [[5 * bs + 1], [1 * bs + 6]])
    write(0, [[2, 3]], [[3 * bs + 2, 3 * bs + 3]])
    write(1, [[5]], [[2 * bs + 5]])                            # a rewrite
    flat = np.asarray(pool).reshape(2, n_blocks, rows, lanes // dim, dim)
    as_tokens = flat.transpose(0, 1, 3, 2, 4).reshape(2, n_blocks * bs, dim)
    np.testing.assert_array_equal(as_tokens, model)
    got = np.asarray(bkv.gather_index_rows(
        pool, 1, jnp.asarray([[2, 4], [5, 0]], jnp.int32)))
    np.testing.assert_array_equal(got[0, 1], np.asarray(pool)[1, 4])


# ---------------------------------------------------------------------------
# (d) the share
# ---------------------------------------------------------------------------

def test_d_eight_shares_add_up_to_the_uncut_layer(ref):
    """The routed parts of the eight shares of a layer of 16 experts (2
    held each, the router over all 16) add up to the uncut reference's
    routed block; nothing is computed on every chip alike here but the
    residual. The program's share (``qwen3_moe``'s ``moe_share``) computes
    the same part."""
    from harness.reference import rms_norm
    uncut = dict(HF, num_experts=16, num_local_experts=16,
                 num_experts_per_tok=4, num_hidden_layers=1)
    w = weights.make_weights(ref.weight_shapes(uncut), seed=2**31 + 52)
    m = rms_norm(jnp.asarray(RNG.normal(size=(2, 9, 64)), jnp.float32),
                 w["model.layers.{i}.post_attention_layernorm.weight"][0],
                 1e-6)
    whole, _ = ref.experts(uncut, w, 0, m)
    parts = []
    for first in range(0, 16, 2):
        share = dict(uncut, num_experts=2, router_num_experts=16,
                     first_expert=first)
        ws = {k: (v[:, first:first + 2] if "{e}" in k else v)
              for k, v in w.items()}
        part, _ = ref.experts(share, ws, 0, m)
        parts.append(np.asarray(part))
        if first in (0, 6):
            app = _app(ref, ws, share)
            moe = app.spec.moe
            assert (moe.num_experts, moe.num_held, moe.first_expert) == \
                (16, 2, first)
            layer_w = jax.tree.map(lambda a: a[0], app.params["layers"])
            got = model_base.moe_block(moe, m, layer_w, phase="prefill")
            np.testing.assert_allclose(np.asarray(got), parts[-1],
                                       atol=1e-7)
    np.testing.assert_allclose(sum(parts), np.asarray(whole), atol=1e-7)
    # ... and no share is the whole: the parts are of the sum's own size
    assert np.abs(np.asarray(whole)).max() > 3e-3
    assert all(np.abs(p - np.asarray(whole)).max() > 1e-3 for p in parts)


@pytest.mark.parametrize("names", ["whole", "share-global-names",
                                   "neither"])
def test_d_a_whole_checkpoint_is_read_at_first_expert(ref, gate_weights,
                                                      names):
    """``qwen3_moe``'s loader, given a checkpoint that names its experts as
    the router does (every expert, or the share alone under its global
    names), reads the share at ``first_expert + e``; a tensor no naming has
    is refused by name."""
    share = dict(HF, num_experts=2, router_num_experts=8, first_expert=4)
    family = get_family("qwen3_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **share))
    view = dict(weights.HfView(ref.weight_shapes(HF), gate_weights,
                               dtype=np.dtype("float32")))
    keep = {"whole": range(8), "share-global-names": (4, 5),
            "neither": (2, 3)}[names]
    view = {k: v for k, v in view.items() if ".experts." not in k
            or int(k.split(".experts.")[1].split(".")[0]) in keep}
    if names == "neither":
        with pytest.raises(KeyError, match=r"experts\.0\.gate_proj"):
            family.convert_hf_state_dict(view, spec)
        return
    host = family.convert_hf_state_dict(view, spec)
    up = np.asarray(
        gate_weights["model.layers.{i}.mlp.experts.{e}.up_proj.weight"],
        np.float32)
    assert host["layers"]["expert_up"].shape == (2, 2, 64, 128)
    np.testing.assert_array_equal(host["layers"]["expert_up"][1, 1],
                                  up[1, 5].T)
    assert host["layers"]["router"].shape == (2, 64, 8)
    with pytest.raises(ValueError, match="held of a router over"):
        family.build_spec(family.config_cls(tcfg, **dict(share,
                                                         first_expert=7)))


# ---------------------------------------------------------------------------
# (e) what a selection refuses, by name
# ---------------------------------------------------------------------------

def test_e_refusals_by_name(ref, gate_weights):
    family = get_family("keye_vl2")

    def spec_of(hf=HF, tp=1, **kw):
        serve = {k: kw.pop(k) for k in list(kw) if k in SERVE}
        tcfg = TpuConfig(tp_degree=tp, dtype="float32",
                         **dict(SERVE, **serve), **kw)
        return family.build_spec(family.config_cls(tcfg, **hf), tp)
    with pytest.raises(NotImplementedError, match="fused decode loop"):
        spec_of(decode_chunk_tokens=4)
    with pytest.raises(NotImplementedError, match="tensor parallelism"):
        spec_of(tp=2)
    with pytest.raises(NotImplementedError, match="speculation"):
        spec_of(speculation_config=SpeculationConfig(speculation_length=3))
    with pytest.raises(NotImplementedError, match="contiguous cache"):
        family.build_spec(family.config_cls(
            TpuConfig(tp_degree=1, dtype="float32", batch_size=2,
                      seq_len=64), **HF))
    with pytest.raises(NotImplementedError, match="indexer_num_kv_heads"):
        spec_of(_hf(indexer_num_kv_heads=2))
    why = model_base.sparse_refusal(["speculation", None, "ragged dispatch"])
    assert "selects for each drafted token" in why and "row kind" in why
    assert set(model_base.SPARSE_UNSUPPORTED) >= {
        "speculation", "ragged dispatch", "fused decode loop",
        "tensor parallelism", "host KV spill / handoff", "contiguous cache"}
    app = _app(ref, gate_weights)
    for kw, name in ((dict(ragged=True), "ragged dispatch"),
                     (dict(speculation=2), "speculation"),
                     (dict(kv_spill_tier=object()),
                      "host KV spill / handoff")):
        with pytest.raises(ConfigurationError, match=name):
            PagedEngineAdapter(app, **kw)
    # the verify, ragged and multi-token steps refuse the spec itself
    z = jnp.zeros((BATCH, 2), jnp.int32)
    bt = jnp.zeros((BATCH, 4), jnp.int32)
    key = jax.random.PRNGKey(0)
    with pytest.raises(NotImplementedError, match="speculation"):
        model_base.paged_spec_verify(app.spec, app.tpu_config, app.params,
                                     app.cache, z, z, z, bt, None, key)
    with pytest.raises(NotImplementedError, match="fused decode loop"):
        model_base.paged_decode_loop(app.spec, app.tpu_config, app.params,
                                     app.cache, z[:, 0], z[:, 0], bt, None,
                                     key, num_steps=2)
    with pytest.raises(NotImplementedError, match="ragged dispatch"):
        model_base.paged_ragged_step(app.spec, app.tpu_config, app.params,
                                     app.cache, z, z, z, bt, z[:, 0],
                                     z[:, 0], None, key)
    # a hand-off carries K and V only
    from neuronx_distributed_inference_tpu.serving.fleet import handoff
    ad = PagedEngineAdapter(app)
    ad.add_requests([1], [S13])
    with pytest.raises(HandoffError, match="index keys"):
        handoff.capture_handoff(ad, 1)


def test_e_a_stack_without_a_selection_keeps_its_programs(ref,
                                                          gate_weights):
    """``qwen3_moe`` on the same weights: no third pool, no ``indexer``
    scope, no ``sparse_attn`` record, and the kernels take no selection."""
    app = _app(ref, gate_weights, family="qwen3_moe")
    ad = PagedEngineAdapter(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    notes = _notes(app)
    assert "sparse_attn" not in notes and "kv_index_pool" not in notes
    assert "index_select" not in notes
    assert "sparse_tokens_selected" not in ad.host_stats
    assert "sparse_dispatches" not in ad.host_stats
    assert set(app.cache) == {"k", "v"}


# ---------------------------------------------------------------------------
# (f) counters, telemetry, the builder's chip check
# ---------------------------------------------------------------------------

def test_f_the_adapter_counts_what_the_rows_select(ref, gate_weights):
    reg = telemetry.MetricsRegistry()
    telemetry.set_registry(reg)
    try:
        app = _app(ref, gate_weights)
        ad = PagedEngineAdapter(app)
        ad.add_requests([1, 2], [Q45, S13])
        selected = cached = 0
        for step in range(4):
            for n in (len(Q45) + step + 1, len(S13) + step + 1):
                selected += min(n, SA["topk"])
                cached += n
            ad.step([1, 2])
        st = ad.host_stats
        assert st["sparse_tokens_selected"] == selected
        assert st["sparse_tokens_cached"] == cached
        assert st["kv_index_pages_held"] == 2 * (-(-(len(Q45) + 4) // 8)
                                                 + -(-(len(S13) + 4) // 8))
        text = reg.render_prometheus()
        assert 'nxdi_sparse_tokens_total{engine="' in text
        assert 'kind="selected"' in text and 'kind="cached"' in text
        assert 'nxdi_kv_pool_pages' in text and 'kind="index"' in text
    finally:
        telemetry.disable()


def test_f_the_warm_up_plan_is_the_five_paged_programs(ref, gate_weights):
    """``precompile`` warms the decode step, the one-row chunk and the pack
    of each width, and no ragged, fused-loop or verify program (refused by
    name: the plan must not trip over the refusal)."""
    from neuronx_distributed_inference_tpu.serving.warmup import precompile
    report = precompile(_app(ref, gate_weights), widths=[1, 16, 32])
    assert [(g["kind"], g["bucket"]) for g in report["graphs"]] == [
        ("paged", 1), ("paged", 16), ("paged_pack", 16), ("paged", 32),
        ("paged_pack", 32), ("carry_ids", BATCH)]
    sites = {k["site"] for k in report["kernels"]}
    assert {"sparse_attn", "kv_index_pool", "paged_decode",
            "paged_prefill"} <= sites


def _toy_file():
    """The toy as a configuration file ``scripts/gate50.py`` and the
    harness's gate can build: the twin's ``topk`` is shrunk FOR THE TWIN."""
    return dict(
        HF, family="keye_vl2", tp=1, dtype="float32", serve=SERVE,
        adapter={"prefill_budget_tokens": 32},
        gate=dict(config={"num_hidden_layers": 2,
                          "sa_config": dict(SA, topk=8)},
                  batch=2, prompt_len=24, new_tokens=8, atol=2e-4, rtol=1e-4,
                  min_positions_held=1.0, median_ratio_max=0.5,
                  worst_ratio_max=1.0, excuse_margin_max=0.0))


def test_f_the_harness_gate_holds_a_toy_twin(ref):
    assert ref.__file__ == os.path.join(ROOT, "benchmark", "references",
                                        "KeyeVL2.py")
    res = build.logit_gate(_toy_file(), seed=2**31 + 50,
                           served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 32 * HF["vocab_size"]


@pytest.mark.parametrize("part", ["gate", "long-walk"])
def test_f_the_builders_chip_check_runs_at_a_toy_size(part):
    """``scripts/gate50.py`` (what PR 50 ran on the CPU backend and on the
    chip at the published widths) at a toy size: the gate passes, every
    control fails it, and the long walk at the file's own ``topk`` (four
    rows of 75 tokens in chunks of 32 through the adapter's budget, a row
    that ends its prompt decoding on beside the others' chunks) holds every
    position, under ``topk`` and past it."""
    spec = importlib.util.spec_from_file_location(
        "gate50", os.path.join(ROOT, "scripts", "gate50.py"))
    gate50 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate50)
    cfg = _toy_file()
    if part == "gate":
        out = gate50.gate_and_controls(cfg, seed=2**31 + 50,
                                       served_precision="highest")
        assert out["sound"]["passed"], out["sound"]
        assert set(out["controls"]) == set(build.load_reference(
            "KeyeVL2").CONTROLS) | {"fp8_weights",
                                    "fp8_weights_vs_reference"}
        assert not any(v["passed"] for v in out["controls"].values()), out
        return
    walk = gate50.long_walk(cfg, seed=2**31 + 50, tokens=75, rows=4,
                            new_tokens=8, block=32,
                            served_precision="highest")
    assert "error" not in walk, walk
    assert walk["topk"] == 16 and min(walk["decode_positions"]) == 8
    assert walk["positions_served"] == 4 * 75 + sum(walk["decode_positions"])
    for part in ("all", "under_topk", "past_topk", "decode"):
        assert walk[part]["held_share"] == 1.0, (part, walk[part])
    assert (BATCH, 1) in walk["program_shapes"]
    assert any(w_ == 32 for _, w_ in walk["program_shapes"])
    assert any(site == "sparse_attn" and why.startswith("masked")
               for site, _, why in walk["notes"])
    for control in gate50.LONG_CONTROLS:
        assert walk["controls"][control]["past_topk"]["held_share"] < 0.9
