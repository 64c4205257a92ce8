"""A decoder-hybrid-decoder on the paged serving path (ISSUE 54):
``phi4flash`` (Phi-4-mini-flash-reasoning: Mamba-1 mixers, differential
attention inside a window and ONE full layer whose pages seven cross layers
read, Gated Memory Units) served through ``PagedEngineAdapter`` at a toy
size on the CPU in float32, every served logit held to the plain reference
``benchmark/references/phi4flash.py``:

  (a) the full forward, as the harness's gate runs it; chunks of two widths
      through the adapter with a window smaller than the prompt, so the ring
      WRAPS, then decode, on the gathered forms and on the two attention
      kernels (interpret mode, heads of 64); a slot's Mamba-1 state and conv
      tail against the reference's, which a bf16-carried state fails;
  (b) a slot released and re-used; a dead row beside live ones;
  (c) the cross layers read the full layer's pages, and nothing else's;
  (d) the placed-query identity of differential attention against the
      four-attention writing;
  (e) the configuration file: every published number, the parameter count
      and the memory arithmetic re-derived from the parameter specs and the
      pools, the toy gate, its controls and faults in the PROGRAM;
  (f) each refusal of the two tables by name; counters, notes, metrics;
  (g) the SERVED chunk form (ISSUE 60; ``output_logits`` off): a chunk's walk
      stops at the full layer, the second decoder, the head and the draw run
      for the one token a row that is sampled from, and not at all in a
      dispatch that samples none - against the whole walk to the last bit of
      every cache (one row and packed), against the reference through chunks
      of both widths, a pack of final and non-final rows, decode; the
      counters; ONE conditional a lowered chunk program, none in the decode
      step's or in another recurrent stack's; three faults.
"""

import functools
import importlib.util
import json
import math
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
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
from neuronx_distributed_inference_tpu.modules import ssm  # noqa: E402
from neuronx_distributed_inference_tpu.ops import attention as attn_ops  # noqa: E402
from neuronx_distributed_inference_tpu.parallel.layers import \
    ParamSpec  # noqa: E402
from neuronx_distributed_inference_tpu.resilience import \
    ConfigurationError  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import (  # noqa: E402
    memory_ledger, precompile)
from neuronx_distributed_inference_tpu.telemetry import \
    metrics as tmetrics  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


def _hf(head=16, **over):
    """Eight layers, every kind under the published index rules: M, window,
    M, window, M (the GMU's source), full, GMU, cross."""
    return dict(dict(
        model_type="phi4flash", hidden_size=8 * head, num_attention_heads=8,
        num_key_value_heads=4, num_hidden_layers=8, intermediate_size=96,
        vocab_size=128, sliding_window=24, layer_norm_eps=1e-5,
        mb_per_layer=2, tie_word_embeddings=True, hidden_act="silu",
        mamba_d_state=8, mamba_dt_rank=8), **over)


HF = _hf()
#: heads of 64: a pair is a 128-lane kv row and both attention kernels
#: take it (interpret mode on the CPU)
HF_KERNEL = _hf(64)
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=128, pa_block_size=8, pa_num_blocks=64,
             context_encoding_buckets=[8, 16], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(54)
#: 93 = 5 x 16 + 13 (a chunk in the 16 bucket, padded): 93 tokens wrap a ring
#: of 6 pages of 8
P93, Q45, R21, S12 = (RNG.integers(1, 128, size=n).tolist()
                      for n in (93, 45, 21, 12))
ATOL = 2e-5
SEED = 2 ** 31 + 54


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("phi4flash")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=SEED)


def _app(ref, w, hf=HF, **serve):
    """``output_logits`` on unless ``serve`` says otherwise: every position's
    logits are handed out, so a chunk walks the whole stack; off is the
    served form (section (g))."""
    family = get_family("phi4flash")
    tcfg = TpuConfig(tp_degree=1, dtype="float32",
                     **{**SERVE, "output_logits": True, **serve})
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


class LogitTap:
    """Every logit the served path computes, by sequence and position."""

    def __init__(self, app):
        self.app, self.by_seq, self.shapes = app, {}, []
        self._inner = app._run_paged
        app._run_paged = self._run

    def _run(self, ids, pos, slots, bt, last, *a, **kw):
        out = self._inner(ids, pos, slots, bt, last, *a, **kw)
        self.shapes.append(tuple(np.shape(ids)))
        owner = {blocks[0]: sid
                 for sid, blocks in self.app.kv_mgr.tables.items()}
        logits = np.asarray(out["logits"])
        pos, slots, bt = np.asarray(pos), np.asarray(slots), np.asarray(bt)
        for r in range(logits.shape[0]):
            for t in np.nonzero(slots[r] >= 0)[0]:
                self.by_seq.setdefault(owner[int(bt[r, 0])], {})[
                    int(pos[r, t])] = logits[r, t]
        return out

    def logits(self, sid, n):
        got = self.by_seq[sid]
        assert sorted(got) == list(range(n)), sorted(got)
        return np.stack([got[p] for p in range(n)])


def _want(ref, w, tokens, hf=HF, control=None):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens]),
                                  control=control))[0]


def _decode(ad, sids, stream, steps):
    for _ in range(steps):
        for sid, tok in ad.step(sids).items():
            stream[sid].append(tok)


def _check(tap, ref, w, sid, prompt, stream, hf=HF, atol=ATOL):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed, hf)
    got = tap.logits(sid, len(fed))
    np.testing.assert_allclose(got, want, atol=atol, rtol=1e-4)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


# ---------------------------------------------------------------------------
# (a) the forward, the walk in chunks, the state
# ---------------------------------------------------------------------------

def test_a_the_cache_is_pools_by_layer_kind_beside_the_state(ref,
                                                             gate_weights):
    app = _app(ref, gate_weights)
    spec = app.spec
    assert spec.layer_kinds == ("mamba", "window", "mamba", "window", "mamba",
                                "full", "gmu", "cross")
    assert spec.diff_attn and spec.window_pool and spec.no_rope
    # a PAIR of published heads is one kv row: 4 kv heads of 16 -> 2 of 32
    assert (spec.num_q_heads, spec.num_kv_heads, spec.head_dim,
            spec.q_proj_size) == (8, 2, 32, 128)
    assert (spec.num_attn_layers, spec.num_window_layers,
            spec.num_ssm_layers) == (3, 2, 3)
    ring = app.window_ring_pages
    assert ring == math.ceil((24 + 16 + 8) / 8) == 6
    shapes = {k: v.shape for k, v in app.cache.items()}
    assert shapes["k"] == shapes["v"] == (1, 65) + shapes["k"][2:]
    assert shapes["k_w"] == shapes["v_w"] == (2, BATCH * ring) \
        + shapes["k"][2:]
    assert shapes["conv_x"] == (3, BATCH, 3, 256)
    assert shapes["ssm"] == (3, BATCH, 8, 256)
    assert app.cache["ssm"].dtype == jnp.float32
    assert set(app.params) == {"embed", "final_norm", "final_norm_b",
                               "layers", "attn_layers", "ssm_layers",
                               "cross_layers", "gmu_layers"}


def test_a_the_full_forward_is_the_references(ref, gate_weights):
    """As ``harness/build.py``'s gate runs it: one full-batch prefill, then
    teacher-forced decode steps."""
    app = _app(ref, gate_weights)
    ids = RNG.integers(1, 128, size=(BATCH, 24)).astype(np.int32)
    res = app.generate(ids[:, :16], max_new_tokens=9, return_logits=True,
                       teacher_tokens=ids[:, 16:])
    steps = res["logits"]
    got = np.concatenate([np.asarray(steps[0])[:, :16]]
                         + [np.asarray(x)[:, -1:] for x in steps[1:9]],
                         axis=1)
    want = np.asarray(ref.forward(HF, gate_weights, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-4)


def test_a_chunks_of_two_widths_wrap_the_ring_then_decode(ref,
                                                          gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P93])[7]]}
    # the ONE-ROW chunk program: 5 x 16 and 13 in the 16 bucket
    assert tap.shapes == [(1, 16)] * 6
    stream[8] = [ad.add_requests([8], [R21])[8]]      # 16 + 5 in the 8 bucket
    assert tap.shapes[6:] == [(1, 16), (1, 8)]
    _decode(ad, [7, 8], stream, 10)
    assert tap.shapes[8:] == [(BATCH, 1)] * 10
    # 93 + 10 tokens through a ring of 6 pages of 8: it wrapped twice
    assert app.window_ring_pages * 8 == 48 < 93
    _check(tap, ref, gate_weights, 7, P93, stream[7])
    _check(tap, ref, gate_weights, 8, R21, stream[8])
    stats = ad.host_stats
    assert stats["state_slots_live"] == 2
    # with every position's logits asked for (ISSUE 60), every chunk
    # walks the whole stack, its second decoder too, and says nothing else
    assert stats["prefill_tokens_cross_decoder"] == \
        stats["prefill_real_tokens"] == 93 + 21
    assert not any(n["site"] == "second_decoder"
                   for n in app.warmup_state()["kernels"])
    # the window pool's counters, over the TWO window layers
    assert stats["kv_window_pages_held"] < stats["kv_window_pages_unwindowed"]
    assert stats["kv_tokens_in_window"] == 2 * 24
    # (a gauge, set at the last dispatch: the rows before its token)
    assert stats["kv_tokens_running"] == 93 + 9 + 21 + 9
    notes = {(n["site"], n["path"], n["reason"])
             for n in app.warmup_state()["kernels"]}
    assert ("kv_shared_pool", "xla",
            "layers=1 readers=2 bytes_a_token=512") in notes
    assert any(s == "kv_window_pool" and "mamba=3 window=2 full=1 cross=1 "
               "gmu=1 window_tokens=24 ring_pages=6" in r
               for s, _, r in notes)
    assert any(s == "recurrent_state" and "kind=mamba1 slot_bytes=" in r
               and "no state-step kernel for kind mamba1" in r
               for s, _, r in notes)


def test_a_the_two_attention_kernels_take_the_pairs(ref):
    """Heads of 64: a pair is a 128-lane kv row, and the decode and the
    prefill kernel (interpret mode) serve the reference's logits through a
    wrapped ring, the shared pool read by the cross layer."""
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=SEED)
    app = _app(ref, w, HF_KERNEL)
    assert app.cache["k"].shape[3:] == (1, 256)       # two pairs share a slot
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [P93])[1]]}
    _decode(ad, [1], stream, 6)
    _check(tap, ref, w, 1, P93, stream[1], HF_KERNEL, atol=1e-4)
    notes = [(n["site"], n["path"], n["reason"])
             for n in app.warmup_state()["kernels"]]
    for site in ("paged_decode", "paged_prefill"):
        mine = [r for s, p, r in notes if s == site]
        assert all(p == "pallas-interpret" for s, p, _ in notes if s == site)
        assert all("form+=diff" in r for r in mine)
        # the full layer, the cross layer on its pool, a window layer's ring
        assert sum("cross: no write" in r for r in mine) >= 1
        assert sum("window=24 ring=6" in r for r in mine) >= 1
    assert ad.host_stats["prefill_dispatches_paged_attn_kernel"] == \
        ad.host_stats["prefill_dispatches"] == 6


def _served_state(ref, w, monkeypatch=None, rounds_to=None):
    """One sequence through the adapter (three chunks, then decode): its
    slot's Mamba-1 state and tail, and the reference's after the same
    tokens. ``rounds_to``: a dtype the state is rounded to whenever it is
    written back (the control)."""
    if rounds_to is not None:
        put = model_base._state_put

        def rounded(arr, li, slots, val):
            if arr.dtype == jnp.float32 and arr.ndim == 4:
                val = val.astype(rounds_to)
            return put(arr, li, slots, val)
        monkeypatch.setattr(model_base, "_state_put", rounded)
    app = _app(ref, w)
    ad = PagedEngineAdapter(app)
    stream = {3: [ad.add_requests([3], [Q45])[3]]}
    _decode(ad, [3], stream, 7)
    fed = jnp.asarray([Q45 + stream[3][:-1]])
    slot = ad._state_slot[3]
    got_s = np.asarray(app.cache["ssm"])[:, slot]            # (Ls, N, C)
    got_t = np.asarray(app.cache["conv_x"])[:, slot]         # (Ls, K-1, C)
    want_s = np.asarray(ref.final_states(HF, w, fed))[:, 0]  # (Ls, C, N)
    want_t = np.asarray(ref.final_tails(HF, w, fed))[:, 0]
    return got_s, np.swapaxes(want_s, 1, 2), got_t, want_t


def test_a_served_slot_holds_the_references_state_and_tail(ref,
                                                           gate_weights):
    got_s, want_s, got_t, want_t = _served_state(ref, gate_weights)
    assert np.abs(got_s - want_s).max() <= 1e-4 * np.abs(want_s).max()
    assert np.abs(got_t - want_t).max() <= 1e-4 * np.abs(want_t).max()


def test_a_bf16_carried_state_fails_the_state_check(ref, gate_weights,
                                                    monkeypatch):
    """The control the logit gate cannot give inside 128 tokens: a state
    rounded to bfloat16 between dispatches misses the reference's by far
    more than the check allows."""
    got_s, want_s, _, _ = _served_state(ref, gate_weights, monkeypatch,
                                        jnp.bfloat16)
    assert np.abs(got_s - want_s).max() > 10 * 1e-4 * np.abs(want_s).max()


# ---------------------------------------------------------------------------
# (b) slots
# ---------------------------------------------------------------------------

def test_b_a_slot_released_and_reused_and_a_dead_row_beside_live_ones(
        ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {s: [t] for s, t in ad.add_requests(
        [1, 2, 3], [Q45, R21, S12]).items()}
    _decode(ad, [1, 2, 3], stream, 4)
    slot = ad._state_slot[2]
    ad.release([2])
    assert ad.host_stats["state_slots_live"] == 2
    # rows 1 and 3 decode on with a DEAD row between them (slot order)
    _decode(ad, [1, 3], stream, 3)
    # a new prompt takes the freed slot: its ring and its state start anew
    stream[4] = [ad.add_requests([4], [P93])[4]]
    assert ad._state_slot[4] == slot
    _decode(ad, [1, 3, 4], stream, 5)
    for sid, prompt in ((1, Q45), (3, S12), (4, P93)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])
    assert ad.host_stats["state_slot_allocs"] == 4
    assert ad.host_stats["state_slot_frees"] == 1
    # only the rows stepped advance: a row left out of a step is dead in it
    _decode(ad, [4], stream, 2)
    _check(tap, ref, gate_weights, 4, P93, stream[4])
    _decode(ad, [1, 3, 4], stream, 2)
    _check(tap, ref, gate_weights, 1, Q45, stream[1])


# ---------------------------------------------------------------------------
# (c) whose pages a cross layer reads
# ---------------------------------------------------------------------------

def test_c_the_cross_layer_reads_the_full_layers_pages(ref, gate_weights):
    """With the full layer's OWN output projection zeroed, its attention
    reaches the logits through nothing: what a perturbed page of ITS pool
    then changes is what the cross layer read there. The rings' pages change
    nothing a decode step's window does not cover."""
    family = get_family("phi4flash")
    app = _app(ref, gate_weights)
    full = app.spec.count_kind("window")       # the full layer's row: last
    layers = dict(app.params["attn_layers"])
    for name in ("o_proj", "o_bias"):
        layers[name] = layers[name].at[full].set(0)
    app.params = dict(app.params, attn_layers=layers)
    ad = PagedEngineAdapter(app)
    ad.add_requests([1], [Q45])

    def logits_after(change):
        cache = dict(app.cache)
        saved = {k: jnp.array(v) for k, v in cache.items()}
        app.cache = change(cache)
        tap = LogitTap(app)
        ad.step([1])
        app._run_paged = tap._inner
        out = tap.by_seq[1][45]
        # roll the step back: the row, its pages and its state as they were
        st = ad.seqs[1]
        st.position -= 1
        st.tokens.pop()
        st.last_token = st.tokens[-1]
        app.cache = saved
        return out
    plain = logits_after(lambda c: c)
    again = logits_after(lambda c: c)
    np.testing.assert_array_equal(plain, again)
    table = app.kv_mgr.tables[1]
    # position 3 of the row, in its first page of the SHARED pool: far
    # outside the window of 24, so no window layer sees position 3 either
    moved = logits_after(lambda c: dict(
        c, v=c["v"].at[0, table[0], 3].add(1.0)))
    assert np.abs(moved - plain).max() > 1e-3
    # the same position's page of a RING (slot 0's first page, both window
    # layers): overwritten long ago or outside the window, nobody reads it
    slot = ad._state_slot[1]
    ring = app.window_ring_pages
    still = logits_after(lambda c: dict(
        c, v_w=c["v_w"].at[:, slot * ring, 3].add(1.0)))
    np.testing.assert_allclose(still, plain, atol=1e-6)
    assert family.family_names == ("phi4flash",)


# ---------------------------------------------------------------------------
# (d) the placed-query identity
# ---------------------------------------------------------------------------

def test_d_placed_queries_over_kv_pairs_are_the_four_attentions(ref):
    """Differential attention as the program serves it - each query placed
    in its half of a pair-wide row, ONE plain grouped-query attention over
    kv pairs, then the combine - against the reference's four plain softmax
    attentions, at random q, k, v, under a window."""
    rng = np.random.default_rng(5)
    b, s, nq, nkv, d = 2, 12, 8, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, n, d)), jnp.float32)
               for n in (nq, nkv, nkv))
    depth, hf = 3, _hf()
    table = ref.weight_shapes(hf)
    w = {name: jnp.asarray(rng.normal(size=e["shape"]) * 0.3 + (
        1.0 if name.endswith("subln.weight") else 0.0), jnp.float32)
        for name, e in table.items() if "inner_cross_attn" in name}
    p = ref.BLOCK["attn"]
    pos = jnp.arange(s)
    mask = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < 5)
    want = ref.diff_attention(hf, w, p, 1, depth, q, k, v, mask)
    placed = model_base._diff_place(q.reshape(b, s, nq * d), nq, 2 * d)
    assert placed.shape == (b, s, nq, 2 * d)
    # head 2j sits in the first half of its row, head 2j + 1 in the second
    np.testing.assert_array_equal(placed[:, :, 0::2, d:], 0)
    np.testing.assert_array_equal(placed[:, :, 1::2, :d], 0)
    out = attn_ops.mha(placed, k.reshape(b, s, nkv // 2, 2 * d),
                       v.reshape(b, s, nkv // 2, 2 * d),
                       jnp.broadcast_to(mask, (b, s, s)), d ** -0.5)
    lw = {"diff_lambda": jnp.stack([w[p + ref.DIFF + "lambda_" + x][1]
                                    for x in ("q1", "k1", "q2", "k2")]),
          "diff_subln": w[p + ref.DIFF + "subln.weight"][1]}
    got = model_base._diff_combine(out, lw, depth)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # pairing by halves (heads j and j + H / 2) is another function
    other = ref.diff_attention(hf, w, p, 1, depth, q, k, v, mask,
                               control="pair_by_halves")
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 0.05


# ---------------------------------------------------------------------------
# (e) the configuration file and the gate
# ---------------------------------------------------------------------------

CELL = "phi4-flash-reason-closed"
CONFIG = "phi-4-mini-flash-reasoning"


def test_e_the_file_keeps_every_published_number():
    cfg = build.load_json("configs", CONFIG + ".json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    published = row["config"]
    assert len(published) == 17
    assert {k: cfg[k] for k in published} == published
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == [] and cfg["family"] == cfg["model_type"]
    assert cfg["chips"] == cfg["tp"] == 1 and cfg["dtype"] == "bfloat16"
    assert cfg["adapter"] == {"prefill_budget_tokens": 256}
    serve = cfg["serve"]
    assert (serve["batch_size"], serve["seq_len"], serve["pa_block_size"],
            serve["pa_num_blocks"], serve["context_encoding_buckets"],
            serve["is_block_kv_layout"], serve["is_prefix_caching"]) == \
        (32, 16384, 32, 16384, [64, 256], True, False)
    assert {"mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
            "mamba_bias", "differential_attention", "layer_kinds",
            "gmu_memory", "projection_bias", "window", "positions",
            "kv_dtype", "ssm_state_dtype", "conv_tail_dtype",
            "tensor_names"} <= set(cfg["assumed"])
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    # EIGHT layers is the fewest that keeps every kind; the window shrunk
    # FOR THE TWIN so that 128 tokens cross it
    assert (twin["num_hidden_layers"], twin["sliding_window"],
            twin["hidden_size"], twin["vocab_size"]) == (8, 64, 2560, 200064)
    ref = build.load_reference("phi4flash")
    assert set(ref.layer_kinds(twin)) == set(ref.KINDS)
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (4, 112, 16)
    assert twin["sliding_window"] < gate["prompt_len"]
    for control in ref.CONTROLS:
        assert control in gate["controls"], control
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "longctx-reason-closed.json")
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] <= cfg["max_position_embeddings"]
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 40.0, 8.0, 54)
    assert mix["prompt_len"] == dict(kind="lognormal", median=4096,
                                     sigma=0.7, lo=512, hi=12288)
    assert mix["output_len"] == dict(kind="lognormal", median=1024,
                                     sigma=0.6, lo=256, hi=4096)
    assert build.load_json("cells", CELL + ".json") == dict(
        config=CONFIG, traffic="longctx-reason-closed", chips=1)
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if CELL in m.get("workloads", ())}
    assert not {"kernel.paged_decode_roofline", "mixer.state_kernel_share",
                "kernel.mixer_decode_roofline",
                "kernel.paged_decode_window_roofline"} & listed
    assert {"step.decode_cross_attn_ms", "step.prefill_cross_attn_ms",
            "step.decode_gmu_ms", "kernel.paged_decode_shared_roofline",
            "prefill.cross_decoder_token_share", "step.decode_attn_ms",
            "step.prefill_attn_ms", "step.decode_mixer_ms",
            "step.prefill_mixer_ms", "step.decode_mlp_ms",
            "kv.window_pages_held_share", "attn.paged_prefill_kernel_share",
            "sched.live_batch_mean", "adapter.prefill_pad_share",
            "adapter.decode_overlap_share", "host.stall_s",
            "device.idle_prep_share", "sched.gaps_behind_prefill_share",
            "sched.stalled_gap_mean_ms",
            "sched.prefill_dispatches_per_stalled_gap"} <= listed
    assert CELL in next(m for m in BENCHMARK["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]


def test_e_the_file_allocates_what_it_says():
    """3,852.6 M parameters recounted from the parameter specs, and the
    pools, the ring and the state of the file's ``memory`` against what the
    program would allocate, as SHAPES (nothing of 11.5 GB is allocated)."""
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        pool_spec, window_pool_spec, window_ring_pages)
    cfg = build.load_json("configs", CONFIG + ".json")
    memory, serve = cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    kinds = spec.layer_kinds
    assert [kinds.count(k) for k in ("mamba", "window", "full", "cross",
                                     "gmu")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" \
        and kinds[18:20] == ("gmu", "cross") and kinds[15] == "window"
    assert (spec.num_q_heads, spec.num_kv_heads, spec.head_dim,
            spec.scale, spec.sliding_window) == (40, 10, 128, 0.125, 512)
    s = spec.ssm
    assert (s.kind, s.d_inner, s.d_state, s.d_conv, s.dt_rank) == \
        ("mamba1", 5120, 16, 4, 160)
    assert (memory["full_layers"], memory["window_layers"],
            memory["shared_pool_readers"]) == (1, 8, 8)
    widest = max(serve["context_encoding_buckets"])
    ring = window_ring_pages(512, widest, serve["pa_block_size"])
    assert ring == memory["window_ring_pages"] == 25
    assert ring * 32 == memory["window_ring_tokens"] == 800
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    wpool = window_pool_spec(spec, serve["batch_size"],
                             serve["pa_block_size"], widest)
    # ten pairs of heads share ONE slot of 1,280 lanes a token
    assert pool.shape == (1, 16385, 32, 1, 1280)
    assert wpool.shape == (8, 32 * 25, 32, 1, 1280)
    assert pool.bytes_per_token == memory["kv_bytes_per_token_per_layer"] \
        == 20 * 64 * 2 * 2
    assert 2 * math.prod(pool.shape) * 2 == memory["global_pool_bytes"]
    assert 2 * math.prod(wpool.shape) * 2 == memory["window_pool_bytes"] \
        == 8 * 5120 * 32 * 800
    state = ssm.ssm_state_shapes(s, spec.num_ssm_layers,
                                 serve["batch_size"], spec.dtype)
    assert state["conv_x"] == ((9, 32, 3, 5120), jnp.bfloat16)
    assert state["ssm"] == ((9, 32, 16, 5120), jnp.float32)
    state_bytes = sum(math.prod(sh) * jnp.dtype(dt).itemsize
                      for sh, dt in state.values())
    assert state_bytes == memory["state_bytes"] == \
        32 * memory["state_slot_bytes"]
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    n = sum(math.prod(ps.shape) for ps in leaves)
    mlp, mamba = 3 * 2560 * 10240, (
        2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 5120 * 16 + 5120 + 5120 * 2560)
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    assert n == memory["parameters"] == (
        200064 * 2560 + 32 * mlp + 9 * mamba + 9 * attn + 7 * cross
        + 7 * 2 * 2560 * 5120 + 65 * 2 * 2560)
    assert round(n / 1e6, 1) == 3852.6
    assert memory["weights_bytes"] == 2 * n
    total = (memory["weights_bytes"] + memory["global_pool_bytes"]
             + memory["window_pool_bytes"] + memory["state_bytes"])
    assert total == memory["before_temps_bytes"]
    assert 0.71 * 16e9 < total < 0.73 * 16e9
    # the reference's table names the same tensors' numbers
    ref = build.load_reference("phi4flash")
    assert sum(math.prod(e["shape"]) for e in ref.weight_shapes(
        build.hf_config(cfg)).values()) == n


def _toy_file():
    """The toy as a configuration file ``scripts/gate54.py`` and the
    harness's gate can build."""
    return dict(
        HF, family="phi4flash", tp=1, dtype="float32",
        serve=dict(SERVE, context_encoding_buckets=[16, 32]),
        adapter={"prefill_budget_tokens": 32},
        gate=dict(config={"num_hidden_layers": 8, "sliding_window": 8},
                  batch=2, prompt_len=24, new_tokens=8, atol=2e-4, rtol=1e-4,
                  min_positions_held=1.0, median_ratio_max=0.5,
                  worst_ratio_max=1.0, excuse_margin_max=0.0))


def _gate54():
    spec = importlib.util.spec_from_file_location(
        "gate54", os.path.join(ROOT, "scripts", "gate54.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_e_the_builders_chip_check_runs_at_a_toy_size(ref):
    """``scripts/gate54.py`` (what PR 54 ran on the CPU backend and on the
    chip at the published widths) at a toy size: the gate passes, every
    control and the fp8-rounded reference fail it, and the long walk at the
    file's own window (three rows of 150 tokens in chunks of 32 through the
    adapter's deferral ON THE SERVED CHUNK FORM, the second decoder run for
    one token a prompt; decode, a released slot taken by a new prompt) holds
    every decode position and every first token."""
    gate54 = _gate54()
    toy = _toy_file()
    out = gate54.gate_and_controls(toy, seed=SEED,
                                   served_precision="highest")
    assert out["sound"]["passed"], out["sound"]
    assert set(out["controls"]) == set(ref.CONTROLS) | {
        "fp8_weights", "fp8_weights_vs_reference"}
    assert not any(v["passed"] for v in out["controls"].values()), {
        k: v["passed"] for k, v in out["controls"].items()}
    walk = gate54.long_walk(toy, seed=SEED, tokens=150, rows=3,
                            new_tokens=8, block=64,
                            served_precision="highest")
    assert walk["window"] == 24 and walk["ring_wraps"] >= 2
    assert walk["slot_reused"] and walk["passed"], walk
    assert walk["decode"]["positions"] == (3 + 1) * 8
    assert walk["decode"]["held_share"] == 1.0
    assert walk["decode"]["worst_ratio"] < 0.5
    assert walk["first_tokens"] == dict(
        prompts=4, held=4, worst_margin=walk["first_tokens"]["worst_margin"])
    # the chunks ran the second decoder for ONE token a row of a dispatch
    # that samples: four of the 17 (a prompt's last chunk each; under the
    # budget of 32 tokens a pass two of them carry another prompt's row)
    stats = walk["host_stats"]
    assert (stats["prefill_dispatches"], stats["prefill_dispatches_sampled"],
            stats["prefill_tokens_cross_decoder"]) == (17, 4, 6)
    assert walk["host_stats"]["prefill_real_tokens"] == 3 * 150 + 37
    assert any(site == "second_decoder" for site, _, _ in walk["notes"])
    assert walk["blocked_vs_plain_reference"] < 1e-5
    assert (3, 32) in walk["program_shapes"] \
        and (1, 32) in walk["program_shapes"]


def test_e_the_reference_found_by_name_gates_a_toy_twin(ref):
    assert ref.__file__ == os.path.join(BENCH, "references", "phi4flash.py")
    toy = _toy_file()
    res = build.logit_gate(toy, seed=SEED, served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 32 * toy["vocab_size"]


@pytest.mark.parametrize("fault", ["lambda", "memory", "cross", "pairs"])
def test_e_a_fault_in_the_program_does_not_pass_the_toy_gate(monkeypatch,
                                                             fault):
    """The other direction of the controls: the PROGRAM broken, the
    reference sound. ``lam`` left at its initial value, the Gated Memory
    Units fed the mixer's GATED output, a cross layer on a window layer's
    ring, the queries placed by halves of the head list."""
    if fault == "lambda":
        combine = model_base._diff_combine
        monkeypatch.setattr(
            model_base, "_diff_combine", lambda out, lw, depth: combine(
                out, dict(lw, diff_lambda=jnp.zeros_like(lw["diff_lambda"])),
                depth))
    elif fault == "memory":
        mixer = ssm.mamba1_mixer

        def gated(s, lw, x, state, **kw):
            out, new = mixer(s, lw, x, state, **kw)
            z = (x @ lw["m1_in"])[..., s.d_inner:]
            return out, dict(new, scan_out=new["scan_out"] * jax.nn.silu(z))
        monkeypatch.setitem(ssm._SSM_BLOCKS, "mamba1", gated)
    elif fault == "cross":
        body = model_base._attn_body

        def elsewhere(spec, h, lw, k_full, v_full, li, ai, *a, **kw):
            if kw.get("cross_kv") is not None:
                ring = ai["ring"]
                kw.update(mixed_local=True)
                # the last window layer's ring in place of the shared pool
                return body(spec, h, lw, elsewhere.ring[0], elsewhere.ring[1],
                            spec.count_kind("window") - 1, ai, *a, **kw)
            out = body(spec, h, lw, k_full, v_full, li, ai, *a, **kw)
            if kw.get("mixed_local"):
                elsewhere.ring = out[1:3]
            return out
        monkeypatch.setattr(model_base, "_attn_body", elsewhere)
        monkeypatch.setattr(model_base, "_attn_block",
                            jax.named_scope("attn")(elsewhere))
    else:
        place = model_base._diff_place

        def by_halves(q, n_heads, head_dim):
            b, t, _ = q.shape
            heads = q.reshape(b, t, 2, n_heads // 2, head_dim // 2)
            return place(jnp.swapaxes(heads, 2, 3).reshape(b, t, -1),
                         n_heads, head_dim)
        monkeypatch.setattr(model_base, "_diff_place", by_halves)
    res = build.logit_gate(_toy_file(), seed=SEED,
                           served_precision="highest")
    assert not res["passed"] and res["worst_ratio"] > 10, res


# ---------------------------------------------------------------------------
# (f) refusals, counters, metrics
# ---------------------------------------------------------------------------

def test_f_refusals_by_name(ref, gate_weights):
    family = get_family("phi4flash")

    def spec_of(hf=HF, **serve):
        tcfg = TpuConfig(tp_degree=serve.pop("tp", 1), dtype="float32",
                         **dict(SERVE, **serve))
        return family.build_spec(family.config_cls(tcfg, **hf))
    # the recurrent table answers first; both name prefix reuse
    with pytest.raises(NotImplementedError, match="prefix caching"):
        spec_of(is_prefix_caching=True)
    with pytest.raises(NotImplementedError, match="fused decode loop"):
        spec_of(decode_chunk_tokens=4)
    with pytest.raises(NotImplementedError,
                       match="sharded decoder-hybrid-decoder"):
        spec_of(tp=2)
    with pytest.raises(NotImplementedError, match="multiple of four"):
        spec_of(_hf(num_hidden_layers=6))
    with pytest.raises(NotImplementedError, match="mb_per_layer"):
        spec_of(_hf(mb_per_layer=4))
    with pytest.raises(ValueError, match="do not pair up"):
        spec_of(_hf(num_key_value_heads=1))
    # off the paged layout the stack is refused, by name
    with pytest.raises(NotImplementedError,
                       match="contiguous decoder-hybrid-decoder"):
        family.build_spec(family.config_cls(
            TpuConfig(tp_degree=1, dtype="float32", batch_size=2,
                      seq_len=64), **HF))
    for table, names in (
            (model_base.RECURRENT_UNSUPPORTED,
             {"prefix caching", "speculation", "ragged dispatch",
              "contiguous decoder-hybrid-decoder",
              "sharded decoder-hybrid-decoder", "host KV spill / handoff"}),
            (model_base.WINDOW_POOL_UNSUPPORTED,
             {"prefix caching", "speculation", "ragged dispatch",
              "tensor parallelism", "host KV spill / handoff"})):
        assert names <= set(table)
    # a layer list that does not hang together is refused by what is missing
    spec = spec_of()
    kw = dict(num_layers=8, layer_kinds=("cross",) + spec.layer_kinds[1:],
              ssm_pattern=(False,) + spec.ssm_pattern[1:],
              layer_pattern=spec.layer_pattern, window_pool=True,
              sliding_window=24, no_rope=True)
    with pytest.raises(ValueError, match="nearest 'full' layer below"):
        model_base._check_layer_kinds(kw, True, 1)
    with pytest.raises(ValueError, match="each one of"):
        model_base._check_layer_kinds(
            dict(kw, layer_kinds=("attn",) * 8), True, 1)
    app = _app(ref, gate_weights)
    for kw, name in ((dict(ragged=True), "ragged dispatch"),
                     (dict(speculation=2), "speculation"),
                     (dict(kv_spill_tier=object()),
                      "host KV spill / handoff")):
        with pytest.raises(ConfigurationError, match=name):
            PagedEngineAdapter(app, **kw)
    with pytest.raises(NotImplementedError, match="transformers"):
        family.load_hf_model("nowhere")
    assert "mamba1" in ssm.CONTINUING_KINDS
    assert ssm.state_kernel_declined(
        app.spec.ssm, app.cache["ssm"], BATCH, 1) == \
        "no state-step kernel for kind mamba1"


def test_f_the_counters_have_their_series(ref, gate_weights):
    telemetry.enable()
    try:
        app = _app(ref, gate_weights)
        ad = PagedEngineAdapter(app)
        stream = {1: [ad.add_requests([1], [Q45])[1]]}
        _decode(ad, [1], stream, 2)
        snap = telemetry.get_registry().snapshot()["metrics"]
        series = snap[tmetrics.PREFILL_TOKENS_CROSS_DECODER_TOTAL]["series"]
        assert sum(s["value"] for s in series) == 45 == \
            ad.host_stats["prefill_tokens_cross_decoder"]
        kinds = {s["labels"]["kind"]: s["value"]
                 for s in snap[tmetrics.KV_POOL_PAGES]["series"]}
        # 46 tokens: 6 pages a layer at full length, a ring holds them all
        assert kinds == {"window": 2 * 6, "global": 1 * 6}
        # the ledger accounts a slot's ring AND its state, each by its own
        ledger = memory_ledger(ad)
        assert ledger["kv"]["pages"]["window_allocated"] == 6 * BATCH * 2
        assert ledger["state"]["live"] == 1
        assert ledger["state"]["slot_bytes"] == 3 * (8 + 3) * 256 * 4
        report = precompile(app)
        # the five paged programs a recurrent stack warms, and no other
        assert sorted((g["kind"], g["bucket"]) for g in report["graphs"]) \
            == [("carry_ids", BATCH), ("paged", 1), ("paged", 8),
                ("paged", 16), ("paged_pack", 8), ("paged_pack", 16)]
    finally:
        telemetry.disable()


def test_f_the_new_metrics_read_the_new_scopes_and_counters():
    names = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name, scope, kind in (
            ("step.decode_cross_attn_ms", "cross_attn", 1),
            ("step.prefill_cross_attn_ms", "cross_attn", 256),
            ("step.decode_gmu_ms", "gmu", 1)):
        spec = build.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == {"kind": "trace_scope_ms", "args": {
            "kind": "paged", "width": kind, "scope": scope}}
        assert names[name]["workloads"] == [CELL]
    share = build.load_json("layer_metrics",
                            "prefill.cross_decoder_token_share.json")
    assert share["reader"] == {"kind": "counter_ratio", "args": {
        "num": ["host_stats.prefill_tokens_cross_decoder"],
        "den": ["host_stats.prefill_real_tokens"], "scale": 100.0}}
    roof = build.load_module(os.path.join(
        BENCH, "layer_metrics", "kernel.paged_decode_shared_roofline.py"))
    cfg = build.load_json("configs", CONFIG + ".json")
    assert roof.readers_and_rings(build.hf_config(cfg)) == (8, 8)
    assert roof.readers_and_rings(dict(build.hf_config(cfg),
                                       num_hidden_layers=8)) == (2, 2)
    assert roof.readers_and_rings({"model_type": "llama"}) is None


# ---------------------------------------------------------------------------
# (g) the served chunk form: the second decoder apart (ISSUE 60)
# ---------------------------------------------------------------------------

class DrawTap:
    """The logits every draw of the SERVED programs reads, by sequence and
    position (``output_logits`` off: a program hands out tokens alone):
    ``model_base._draw`` behind a host callback, which a second decoder that
    is skipped never reaches. ``heads`` counts the head's runs a dispatch,
    ``lasts`` keeps each dispatch's ``last_idx``."""

    def __init__(self, monkeypatch, app):
        self.app, self.by_seq, self.shapes, self.heads = app, {}, [], []
        self._seen, self.lasts = [], []
        draw = model_base._draw

        def tapped(cfg, logits, *a):
            jax.debug.callback(lambda x: self._seen.append(np.asarray(x)),
                               logits)
            return draw(cfg, logits, *a)
        monkeypatch.setattr(model_base, "_draw", tapped)
        self._inner = app._run_paged
        app._run_paged = self._run

    def _run(self, ids, pos, slots, bt, last, *a, **kw):
        out = self._inner(ids, pos, slots, bt, last, *a, **kw)
        self.tokens = np.asarray(out["tokens"])
        jax.effects_barrier()
        assert "logits" not in out
        self.shapes.append(tuple(np.shape(ids)))
        self.heads.append(len(self._seen))
        self.lasts.append(np.asarray(last).tolist())
        owner = {blocks[0]: sid
                 for sid, blocks in self.app.kv_mgr.tables.items()}
        pos, slots, bt = np.asarray(pos), np.asarray(slots), np.asarray(bt)
        for logits in self._seen:
            for r, t in enumerate(np.asarray(last)):
                if t >= 0 and slots[r, t] >= 0:
                    self.by_seq.setdefault(owner[int(bt[r, 0])], {})[
                        int(pos[r, t])] = logits[r]
        self._seen.clear()
        return out


def _check_served(tap, ref, w, sid, prompt, stream, hf=HF, atol=ATOL):
    """What the served form hands out of ``sid``: the first token and every
    decode step's logits, against the reference over the same tokens."""
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed, hf)[len(prompt) - 1:]
    got = tap.by_seq[sid]
    assert sorted(got) == list(range(len(prompt) - 1, len(fed))), sorted(got)
    np.testing.assert_allclose(np.stack([got[p] for p in sorted(got)]), want,
                               atol=atol, rtol=1e-4)
    assert stream == want.argmax(-1).tolist()


def _serve_two_prompts(ref, w, monkeypatch):
    """P93 (5 x 16 and 13 in the 16 bucket) and R21 (16, then 5 in the 8
    bucket) through the one-row chunk programs of the served form, then ten
    decode steps, every draw's logits held to the reference."""
    app = _app(ref, w, output_logits=False)
    ad = PagedEngineAdapter(app)
    tap = DrawTap(monkeypatch, app)
    stream = {7: [ad.add_requests([7], [P93])[7]]}
    stream[8] = [ad.add_requests([8], [R21])[8]]
    assert tap.shapes == [(1, 16)] * 7 + [(1, 8)]
    # a chunk that is not its prompt's last says so; the head ran where a
    # prompt's LAST chunk went, and nowhere else
    assert tap.lasts == [[-1]] * 5 + [[12], [-1], [4]]
    assert tap.heads == [0] * 5 + [1, 0, 1]
    _decode(ad, [7, 8], stream, 10)
    assert tap.heads[8:] == [1] * 10
    _check_served(tap, ref, w, 7, P93, stream[7])
    _check_served(tap, ref, w, 8, R21, stream[8])
    return app, ad


#: what each case of the bit-for-bit comparison admits at once, with the
#: dispatches it takes: a prompt of six one-row chunks; three prompts in two
#: full-batch packs (rows in slot order, a dead row beside them, a row that
#: samples beside rows that do not) and Q45's last chunk alone
_WALKS = {"one_row": ({7: P93}, [(1, 16)] * 6, [0] * 5 + [1]),
          "packed": ({1: Q45, 2: R21, 3: S12},
                     [(BATCH, 16), (BATCH, 16), (1, 16)], [1, 1, 1])}


@pytest.mark.parametrize("walk", sorted(_WALKS))
def test_g_the_chunk_apart_is_the_whole_walk_to_the_last_bit(
        ref, gate_weights, monkeypatch, walk):
    """The same prompts (three chunks or more) through the whole walk and
    through the served form, one row at a time and packed: every pool, ring,
    state and tail equal to the last bit after the chunks and after decode
    steps (the first decoder is the same instructions), the same tokens, and
    the sampled positions' logits within 1e-5 of the whole walk's."""
    prompts, shapes, heads = _WALKS[walk]
    both = {}
    for name in ("whole", "served"):
        app = _app(ref, gate_weights, output_logits=name == "whole")
        ad = PagedEngineAdapter(app)
        tap = (LogitTap(app) if name == "whole"
               else DrawTap(monkeypatch, app))
        stream = {s: [t] for s, t in ad.add_requests(
            list(prompts), list(prompts.values())).items()}
        assert tap.shapes == shapes
        after_chunks = {k: np.asarray(v) for k, v in app.cache.items()}
        _decode(ad, list(prompts), stream, 3)
        both[name] = (stream, after_chunks,
                      {k: np.asarray(v) for k, v in app.cache.items()}, tap)
    assert both["whole"][0] == both["served"][0]
    assert set(both["whole"][1]) == {"k", "v", "k_w", "v_w", "conv_x", "ssm"}
    for at in (1, 2):
        for k, v in both["whole"][at].items():
            np.testing.assert_array_equal(v, both["served"][at][k], err_msg=k)
    whole, served = both["whole"][3], both["served"][3]
    for sid, prompt in prompts.items():
        n = len(prompt)
        assert sorted(served.by_seq[sid]) == list(range(n - 1, n + 3))
        for p, got in served.by_seq[sid].items():
            np.testing.assert_allclose(got, whole.by_seq[sid][p], atol=1e-5,
                                       rtol=0)
    # the head ran in each dispatch that samples and in the decode steps
    assert served.heads == heads + [1] * 3


def test_g_a_dispatch_that_samples_nothing_takes_the_empty_branch(
        ref, gate_weights, monkeypatch):
    """By the step function itself: a chunk whose rows all carry a negative
    ``last_idx`` writes its caches and states as a sampling one does, never
    reaches the head and hands out zeros, which nobody fetches. Pad rows are
    clones of row 0 (a program of two rows over ONE state slot): beside a
    row that samples nothing they sample nothing, beside one that samples
    they draw its token."""
    served = _app(ref, gate_weights, output_logits=False)
    ad = PagedEngineAdapter(served)
    tap = DrawTap(monkeypatch, served)
    ad.add_requests([7], [P93[:40]])
    table = served.kv_mgr.block_table_array([7], served.max_blocks)
    pos = 40 + np.arange(16, dtype=np.int32)[None]
    ids = np.asarray([P93[40:56]], np.int32)
    slots = table[0, pos // 8] * 8 + pos % 8
    slot = np.asarray([ad._state_slot[7]], np.int32)

    def run(rows, last):
        before = served.cache
        served.cache = jax.tree.map(jnp.copy, before)
        served._run_paged(*(np.repeat(x, rows, 0)
                            for x in (ids, pos, slots, table)),
                          np.full((rows,), last, np.int32),
                          state_slots=np.repeat(slot, rows))
        after, served.cache = served.cache, before
        return ({k: np.asarray(v) for k, v in after.items()},
                tap.heads[-1], tap.tokens)
    empty, heads, tokens = run(1, -1)
    assert heads == 0 and (tokens == 0).all()
    sampled, heads, token = run(1, 15)
    assert heads == 1 and token.shape == (1,)
    for rows, last, ran in ((2, -1, 0), (2, 15, 1)):
        cloned, heads, tokens = run(rows, last)
        assert heads == ran and (tokens == (token[0] if ran else 0)).all()
        for k, v in empty.items():
            np.testing.assert_array_equal(v, sampled[k], err_msg=k)
            np.testing.assert_array_equal(v, cloned[k], err_msg=k)


def test_g_chunks_of_both_widths_then_decode_are_the_references(
        ref, gate_weights, monkeypatch):
    """The served form against the plain reference, its non-final chunks on
    the empty branch; the counters: ONE token a dispatch that samples, the
    dispatches by branch; one program a (kind, width) as before."""
    telemetry.enable()
    try:
        app, ad = _serve_two_prompts(ref, gate_weights, monkeypatch)
        stats = ad.host_stats
        assert stats["prefill_real_tokens"] == 93 + 21
        assert (stats["prefill_dispatches"],
                stats["prefill_dispatches_sampled"],
                stats["prefill_tokens_cross_decoder"]) == (8, 2, 2)
        snap = telemetry.get_registry().snapshot()["metrics"]
        series = snap[tmetrics.PREFILL_TOKENS_CROSS_DECODER_TOTAL]["series"]
        assert sum(s["value"] for s in series) == 2
    finally:
        telemetry.disable()
    notes = {(n["site"], n["reason"]) for n in app.warmup_state()["kernels"]}
    for width in (8, 16):
        assert ("second_decoder",
                "apart: layers 6-7, the head and the draw on one token a row "
                f"of {width}, where a row samples") in notes
    # one program a (kind, width), as before: the five a recurrent stack has
    report = precompile(app)
    assert sorted((g["kind"], g["bucket"]) for g in report["graphs"]) \
        == [("carry_ids", BATCH), ("paged", 1), ("paged", 8), ("paged", 16),
            ("paged_pack", 8), ("paged_pack", 16)]


def test_g_a_pack_of_final_and_non_final_rows(ref, gate_weights,
                                              monkeypatch):
    """Three prompts at once: the full-batch pack, rows in slot order with a
    dead row beside them. The first pack holds S12's LAST chunk beside
    chunks of Q45 and R21 that sample nothing, the second R21's last beside
    Q45's second, the third is Q45's last alone; each prompt's first token
    and decode logits are the reference's, and the counter counts the rows
    of the three dispatches, all of which sample."""
    app = _app(ref, gate_weights, output_logits=False)
    ad = PagedEngineAdapter(app)
    tap = DrawTap(monkeypatch, app)
    prompts = {1: Q45, 2: R21, 3: S12}
    stream = {s: [t] for s, t in ad.add_requests(
        list(prompts), list(prompts.values())).items()}
    assert tap.shapes == [(BATCH, 16), (BATCH, 16), (1, 16)]
    assert tap.heads == [1, 1, 1]
    # rows by state slot: a dead row and a row that samples nothing say -1
    assert sorted(tap.lasts[0]) == [-1, -1, -1, 11]
    assert sorted(tap.lasts[1]) == [-1, -1, -1, 4] and tap.lasts[2] == [12]
    assert (ad.host_stats["prefill_dispatches_sampled"],
            ad.host_stats["prefill_tokens_cross_decoder"]) == (3, 3 + 2 + 1)
    _decode(ad, [1, 2, 3], stream, 4)
    for sid, prompt in prompts.items():
        _check_served(tap, ref, gate_weights, sid, prompt, stream[sid])
    # packs in which NO row samples: three prompts' first chunks
    ad.release([1, 2, 3])
    before = len(tap.heads)
    sampled = ad.host_stats["prefill_dispatches_sampled"]
    ad.add_requests([4, 5, 6], [P93, Q45, P93[:40]])
    packs = list(zip(tap.shapes[before:], tap.heads[before:]))
    assert packs[0] == ((BATCH, 16), 0) and packs[1] == ((BATCH, 16), 0)
    assert ((BATCH, 16), 1) in packs          # Q45's and P93[:40]'s last
    assert ad.host_stats["prefill_dispatches_sampled"] - sampled \
        == sum(heads for _, heads in packs) < len(packs)


def _lowered(app, rows, width):
    """The lowered text of ``app``'s paged step of ``rows`` x ``width``."""
    i32 = jnp.int32
    kw = ({"state_slots": jnp.zeros((rows,), i32)}
          if rows != app.tpu_config.batch_size else {})
    return jax.jit(functools.partial(
        model_base.paged_forward_step, app.spec, app.tpu_config)).lower(
        app.params, app.cache, *(jnp.zeros((rows, width), i32),) * 2,
        jnp.full((rows, width), -1, i32),
        jnp.zeros((rows, app.max_blocks), i32), jnp.zeros((rows,), i32),
        None, jax.random.PRNGKey(0), **kw).as_text()


def _conditionals(text):
    return len(re.findall(r"\bstablehlo\.(?:case|if)\b", text))


def test_g_one_conditional_a_chunk_program_and_none_with_every_logit(
        ref, gate_weights):
    """A static choice by what the program hands out and by the spec's
    ``layer_kinds``, no option: the lowered chunk programs (one row, the
    pack) hold exactly ONE conditional with ``output_logits`` off and none
    with it on (the whole walk over every token); the decode step holds none
    either way."""
    served = _app(ref, gate_weights, output_logits=False)
    assert [_conditionals(_lowered(served, *shape)) for shape in
            ((1, 16), (1, 8), (BATCH, 16), (BATCH, 1))] == [1, 1, 1, 0]
    whole = _app(ref, gate_weights)
    assert [_conditionals(_lowered(whole, *shape)) for shape in
            ((1, 16), (BATCH, 16), (BATCH, 1))] == [0, 0, 0]
    assert model_base.second_decoder_start(served.spec) == 6
    # a stack whose LAST layer writes a cache has no second decoder
    kinds = served.spec.layer_kinds
    for tail, start in ((("cross", "full"), None), (("full", "cross"), 7),
                        (("gmu", "gmu"), 6)):
        spec = served.spec.__class__(**dict(
            served.spec.__dict__, layer_kinds=kinds[:6] + tail))
        assert model_base.second_decoder_start(spec) == start
    assert model_base.walk_part(served.spec, {}, 16)[:2] == (0, 8)
    assert model_base.walk_part(
        served.spec, {"sampled": jnp.zeros((1,), jnp.int32)}, 1)[:2] == (0, 8)


@pytest.mark.parametrize("stack", ["test_recurrent_paged",
                                   "test_olmo_hybrid_paged",
                                   "test_qwen3_next_paged"])
def test_g_a_stack_without_a_second_decoder_lowers_no_conditional(stack):
    """granite's, olmo-hybrid's and qwen3-next's walk is this one
    (``run_layers_ssm``) and their step ``paged_forward_step``: without
    ``layer_kinds`` that end in layers which write nothing, the served chunk
    programs and the decode step lower to NO conditional, nothing is
    gathered before the last layer, and the adapter's chunk rows that
    sample nothing say 0 as before."""
    toy = importlib.import_module(stack)
    family = get_family(toy.HF["model_type"])
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **toy.SERVE)
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **toy.HF),
                                   family).init_random_weights().init_cache()
    assert app.spec.layer_kinds is None and not tcfg.output_logits
    assert model_base.second_decoder_start(app.spec) is None
    assert model_base.walk_part(app.spec, {}, 16) == (
        0, app.spec.num_layers, None, None)
    for shape in ((1, 16), (toy.BATCH, 16), (toy.BATCH, 1)):
        text = _lowered(app, *shape)
        assert _conditionals(text) == 0, shape
    lasts = []
    inner = app._run_paged
    app._run_paged = lambda i, p, s, b, last, *a, **kw: (
        lasts.append(np.asarray(last).tolist()),
        inner(i, p, s, b, last, *a, **kw))[1]
    ad = PagedEngineAdapter(app)
    ad.add_requests([7], [P93[:37]])
    width = max(toy.SERVE["context_encoding_buckets"])
    assert lasts == [[0]] * (36 // width) + [[36 % width]]
    assert "prefill_dispatches_sampled" not in ad.host_stats


def _second_decoder_at_the_token_before(monkeypatch):
    tokens_of = model_base.second_decoder_tokens

    def broken(spec, cfg, params, cache, hidden, handed, pos, slots, bt,
               last, *a):
        return tokens_of(spec, cfg, params, cache, hidden, handed, pos,
                         slots, bt, jnp.where(last > 0, last - 1, last), *a)
    monkeypatch.setattr(model_base, "second_decoder_tokens", broken)


def _scan_output_of_the_last_column(monkeypatch):
    tokens_of = model_base.second_decoder_tokens

    def broken(spec, cfg, params, cache, hidden, handed, *a):
        start, shared, memory = handed
        return tokens_of(spec, cfg, params, cache, hidden, (
            start, shared, jnp.broadcast_to(memory[:, -1:], memory.shape)),
            *a)
    monkeypatch.setattr(model_base, "second_decoder_tokens", broken)


def _no_second_decoder_for_the_sampled_token(monkeypatch):
    walk = model_base.run_layers_ssm

    def skipping(spec, params, cache, hidden, *a, part=None, **kw):
        if part is not None:      # the sampled token's walk: layers 6-7
            return hidden, cache, {}
        return walk(spec, params, cache, hidden, *a, **kw)
    monkeypatch.setattr(model_base, "run_layers_ssm", skipping)


@pytest.mark.parametrize("fault", [_second_decoder_at_the_token_before,
                                   _scan_output_of_the_last_column,
                                   _no_second_decoder_for_the_sampled_token],
                         ids=lambda f: f.__name__.strip("_"))
def test_g_a_fault_in_the_sampled_tokens_walk_fails_the_reference(
        ref, gate_weights, monkeypatch, fault):
    """The controls of the comparison above: the second decoder run at the
    WRONG token (the one before the sampled one); the Gated Memory Unit
    gating the scan output's LAST column of the padded width (P93's last
    chunk is 13 tokens in the 16 bucket); the second decoder SKIPPED for the
    sampled token too (the head on the first decoder's output) - each misses
    the reference's first token logits by far more than the check allows."""
    fault(monkeypatch)
    with pytest.raises(AssertionError, match="Mismatched elements"):
        _serve_two_prompts(ref, gate_weights, monkeypatch)
