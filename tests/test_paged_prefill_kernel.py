"""The paged prefill kernel (ISSUE 49, ``ops/paged_prefill.py``): a chunk of
T > 1 queries a row over the row's live pages of the K / V pools (a window
layer: its ring), its own K / V among them, in interpret mode on the CPU
against the XLA form ``model_base._attn_block`` keeps for declines (the gather
of the whole table and ``attention.mha`` under the mask); every branch of
``declined``; the engagement record's text and what the adapter's counter
reads from it; the call site inside ``_attn_block``; the timing script's
floor and its refusal to print a time without a chip."""

import contextlib
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
from neuronx_distributed_inference_tpu.ops import attention as attn_ops
from neuronx_distributed_inference_tpu.ops import kernel_mode, paged_prefill
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh)

REPO = Path(__file__).resolve().parent.parent
BS = 8

#: name -> (query heads, kv heads, head lanes): the attention of the five
#: non-latent configurations under ``benchmark/configs`` (olmo-hybrid's 30
#: heads as the pool's 32 head slots carry them)
HEADS = {
    "mha-16x128": (16, 16, 128),
    "gqa-28-over-4x128": (28, 4, 128),
    "fold2-heads-of-64": (32, 8, 64),
    "2-kv-heads-of-256": (16, 2, 256),
    "30-heads-in-32-slots": (32, 32, 128),
}


def _spec(hq=28, hkv=4, d=128, **over):
    return SimpleNamespace(**{**dict(
        gqa=SimpleNamespace(num_q_heads=hq, num_kv_heads=hkv, tp=1),
        head_dim=d, scale=0.17, kv_scale=None, attn_soft_cap=None,
        attn_sink=False, alibi=False, attn_chunk=0, sliding_window=0,
        decode_kernel=None), **over})


def _case(rng, firsts, t, hq, hkv, d, dtype, mb=12, layers=3):
    """Rows of ``t`` queries at ``firsts`` over pools of scattered pages,
    stored as the application stores them (``bkv.pool_page``), block 0 the
    null block; the chunk's own K / V are what the pool holds there."""
    b = len(firsts)
    slots, lanes = bkv.pool_page(hkv, d)
    shape = (layers, 1 + b * mb, BS, slots, lanes)
    k, v = rng.normal(size=shape), rng.normal(size=shape)
    k[:, 0] = v[:, 0] = 0
    table = np.stack([1 + r * mb + rng.permutation(mb) for r in range(b)])
    return dict(
        q=jnp.asarray(rng.normal(size=(b, t, hq, d)), dtype),
        k=jnp.asarray(k, dtype), v=jnp.asarray(v, dtype),
        table=jnp.asarray(table, jnp.int32),
        pos=jnp.asarray(np.asarray(firsts)[:, None] + np.arange(t)[None],
                        jnp.int32))


def _xla_form(x, d, scale, mask, table=None, soft_cap=None):
    """``gathered_mha``: the whole table's rows, their lanes split into
    heads, under the mask."""
    def gathered(pool):
        rows = bkv.gather_layer_kv(pool, 1,
                                   x["table"] if table is None else table)
        return rows.reshape(rows.shape[:2] + (-1, d))
    return attn_ops.mha(x["q"], gathered(x["k"]), gathered(x["v"]), mask,
                        scale, logits_soft_cap=soft_cap)


def _close(got, want, dtype, **kw):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if dtype == jnp.float32 else 4e-2, **kw)


#: name -> each row's first position; a block is 12 pages of 8 tokens here,
#: and 2 pages where the table is walked in several blocks
PREFIXES = {
    "head-of-prompt": [0, 0],
    "ends-inside-a-page": [13, 37],
    "rows-differ": [0, 61],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("prefix", sorted(PREFIXES))
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_the_kernel_agrees_with_the_gathered_form(heads, prefix, dtype):
    """Every page shape of the benchmark's five non-latent configurations:
    a kv row cut by lanes (4 x 128, 2 x 256), kv rows de-interleaved by a
    strided read (16 and 32 a token), narrow heads placed in their lanes."""
    hq, hkv, d = HEADS[heads]
    rng = np.random.default_rng(len(heads) + len(prefix))
    t = 16
    x = _case(rng, PREFIXES[prefix], t, hq, hkv, d, dtype)
    got = paged_prefill.paged_prefill_attention(
        x["q"], x["k"], x["v"], 1, x["pos"][:, 0], x["table"], scale=0.17,
        interpret=True)
    assert got.shape == (2, t, hq, d) and got.dtype == dtype
    want = _xla_form(x, d, 0.17, attn_ops.decode_mask(x["pos"], 12 * BS))
    _close(got, want, dtype)


@pytest.mark.parametrize("heads", ["mha-16x128", "gqa-28-over-4x128"])
def test_a_walk_of_several_blocks_and_tiles(heads, monkeypatch):
    """The block shrunk to two pages and the tile to half the kv rows: the
    loop over blocks, both slots and the grid over tiles are walked."""
    hq, hkv, d = HEADS[heads]
    rng = np.random.default_rng(5)
    t = 16
    x = _case(rng, [50, 3], t, hq, hkv, d, jnp.float32)
    monkeypatch.setattr(paged_prefill, "PAGED_PREFILL_BLOCK_TOKENS", 2 * BS)
    monkeypatch.setattr(paged_prefill, "PAGED_PREFILL_TILE_ROWS",
                        hq * t // (2 if hkv == 16 else 1))
    plan = paged_prefill.prefill_plan(hq, d, x["k"], t, 12)
    assert plan.pages == 2 and plan.tile == (8 if hkv == 16 else 1)
    got = paged_prefill.paged_prefill_attention(
        x["q"], x["k"], x["v"], 1, x["pos"][:, 0], x["table"], scale=0.17,
        interpret=True)
    _close(got, _xla_form(x, d, 0.17,
                          attn_ops.decode_mask(x["pos"], 12 * BS)),
           jnp.float32)


def test_a_pack_with_a_dead_row_and_rows_of_different_lengths():
    """A full-batch pack as the adapter builds it for a recurrent stack: a
    dead row (``slot_mapping`` < 0 throughout: a null block table, positions
    from 1) between rows of different prefixes. Every value is finite and
    each live row is what it is alone; the rows are the kernel's grid."""
    hq, hkv, d = HEADS["fold2-heads-of-64"]
    rng = np.random.default_rng(9)
    t = 16
    x = _case(rng, [40, 1, 0, 66], t, hq, hkv, d, jnp.float32)
    table = x["table"].at[1].set(0)
    pack = paged_prefill.paged_prefill_attention(
        x["q"], x["k"], x["v"], 1, x["pos"][:, 0], table, scale=0.17,
        interpret=True)
    assert np.isfinite(np.asarray(pack)).all()
    want = _xla_form(x, d, 0.17, attn_ops.decode_mask(x["pos"], 12 * BS),
                     table=table)
    for r in (0, 2, 3):
        _close(pack[r], want[r], jnp.float32)
        alone = paged_prefill.paged_prefill_attention(
            x["q"][r:r + 1], x["k"], x["v"], 1, x["pos"][r:r + 1, 0],
            table[r:r + 1], scale=0.17, interpret=True)
        np.testing.assert_allclose(np.asarray(pack[r]), np.asarray(alone[0]),
                                   atol=1e-6)


def test_a_pad_query_past_the_table_reads_inside_it():
    """Pad columns carry ``first + t`` whatever the table holds: the real
    queries agree with the gathered form, every value is finite."""
    hq, hkv, d = HEADS["gqa-28-over-4x128"]
    rng = np.random.default_rng(7)
    t, mb = 16, 4                                  # the table holds 32 tokens
    x = _case(rng, [20, 3], t, hq, hkv, d, jnp.float32, mb=mb)
    got = paged_prefill.paged_prefill_attention(
        x["q"], x["k"], x["v"], 1, x["pos"][:, 0], x["table"], scale=0.17,
        interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    want = _xla_form(x, d, 0.17, attn_ops.decode_mask(x["pos"], mb * BS))
    _close(got[1], want[1], jnp.float32)
    _close(got[0, :12], want[0, :12], jnp.float32)   # 20..31 in the table


def test_a_soft_cap_is_the_gathered_forms():
    hq, hkv, d = HEADS["gqa-28-over-4x128"]
    rng = np.random.default_rng(13)
    x = _case(rng, [9, 30], 16, hq, hkv, d, jnp.float32)
    got = paged_prefill.paged_prefill_attention(
        x["q"], x["k"], x["v"], 1, x["pos"][:, 0], x["table"], scale=0.17,
        soft_cap=5.0, interpret=True)
    _close(got, _xla_form(x, d, 0.17,
                          attn_ops.decode_mask(x["pos"], 12 * BS),
                          soft_cap=5.0), jnp.float32)


# ---------------------------------------------------------------------------
# a window layer: the ring, the traced window
# ---------------------------------------------------------------------------

def _ring_case(rng, firsts, t, window, hq, hkv, d, slots_of=None):
    """A window pool of ``len(firsts)`` ring slots, written as the serving
    path writes it: every position ``< first + t`` of a row at ring page
    ``(pos // BS) % R`` of its slot, later positions over earlier ones - so
    a ring that has WRAPPED holds the newest token of each of its slots.
    Returns the case and ``window_ring_inputs``' arrays for the chunk."""
    b = len(firsts)
    ring = bkv.window_ring_pages(window, t, BS)
    slots, lanes = bkv.pool_page(hkv, d)
    k = np.zeros((3, b * ring, BS, slots, lanes), np.float32)
    v = np.zeros_like(k)
    slot_of = list(range(b)) if slots_of is None else slots_of
    for r, first in enumerate(firsts):
        for pos in range(first + t):
            page = slot_of[r] * ring + (pos // BS) % ring
            k[1, page, pos % BS] = rng.normal(size=(slots, lanes))
            v[1, page, pos % BS] = rng.normal(size=(slots, lanes))
    pos = jnp.asarray(np.asarray(firsts)[:, None] + np.arange(t)[None],
                      jnp.int32)
    mb = -(-(max(firsts) + t) // BS) + 2
    x = dict(q=jnp.asarray(rng.normal(size=(b, t, hq, d)), jnp.float32),
             k=jnp.asarray(k), v=jnp.asarray(v), pos=pos)
    ri = model_base.window_ring_inputs(
        SimpleNamespace(sliding_window=window), x["k"], b, pos, pos,
        jnp.zeros((b, mb), jnp.int32),
        None if slots_of is None else jnp.asarray(slot_of, jnp.int32))
    return x, ri, ring


@pytest.mark.parametrize("window, firsts", [
    (32, [200, 77]),          # the ring (7 pages) has wrapped many times
    (32, [0, 40]),            # and has not: the head of a prompt
    (8, [100, 3]),            # a window shorter than the width (16)
], ids=["wrapped", "not-wrapped", "window-shorter-than-the-width"])
def test_a_window_layer_reads_its_ring(window, firsts):
    """The kernel's table is the ring as LOGICAL pages (``kernel_table``),
    the window a scalar in SMEM; the gathered form reads the ``R`` pages
    that end at the chunk's last page under ``window_ring_inputs``' mask.
    The gate's twin (window 64 against a width of 256) is the third case's
    shape: queries whose window starts inside the chunk."""
    hq, hkv, d = HEADS["gqa-28-over-4x128"]
    rng = np.random.default_rng(window + firsts[0])
    x, ri, ring = _ring_case(rng, firsts, 16, window, hq, hkv, d)
    assert ring == (window + 16 + BS) // BS
    got = paged_prefill.paged_prefill_attention(
        x["q"], x["k"], x["v"], 1, x["pos"][:, 0], ri["kernel_table"],
        scale=0.17, window=window, interpret=True)
    want = _xla_form(x, d, 0.17, ri["mask"], table=ri["table"])
    _close(got, want, jnp.float32)


def test_a_rows_ring_is_its_state_slots():
    """The one-row chunk program: row 0 owns ring slot 2 of 3."""
    hq, hkv, d = HEADS["gqa-28-over-4x128"]
    rng = np.random.default_rng(21)
    full, _, ring = _ring_case(rng, [5, 9, 150], 16, 32, hq, hkv, d)
    pos = full["pos"][2:3]
    ri = model_base.window_ring_inputs(
        SimpleNamespace(sliding_window=32), full["k"], 3, pos, pos,
        jnp.zeros((1, 24), jnp.int32), jnp.asarray([2], jnp.int32))
    x = dict(full, q=full["q"][2:3])
    got = paged_prefill.paged_prefill_attention(
        x["q"], x["k"], x["v"], 1, pos[:, 0], ri["kernel_table"],
        scale=0.17, window=32, interpret=True)
    _close(got, _xla_form(x, d, 0.17, ri["mask"], table=ri["table"]),
           jnp.float32)


@pytest.mark.parametrize("local", [False, True], ids=["global", "local"])
def test_a_traced_window_is_the_layers(local):
    """Under a ``layer_pattern`` on one pool the window is a traced
    ``where(is_local, window, 0)``: one compiled call serves both kinds."""
    hq, hkv, d = HEADS["mha-16x128"]
    rng = np.random.default_rng(17)
    x = _case(rng, [70, 21], 16, hq, hkv, d, jnp.float32)

    @jax.jit
    def layer(is_local):
        return paged_prefill.paged_prefill_attention(
            x["q"], x["k"], x["v"], 1, x["pos"][:, 0], x["table"],
            scale=0.17, window=jnp.where(is_local, 24, 0), interpret=True)
    want = _xla_form(x, d, 0.17, attn_ops.decode_mask(
        x["pos"], 12 * BS, window=24 if local else 0))
    _close(layer(jnp.asarray(local)), want, jnp.float32)


# ---------------------------------------------------------------------------
# the plan, the declines, the record
# ---------------------------------------------------------------------------

def _pool(hkv=4, d=128, dtype=jnp.bfloat16, bs=32):
    slots, lanes = bkv.pool_page(hkv, d)
    return jax.ShapeDtypeStruct((2, 65, bs, slots, lanes), dtype)


@pytest.mark.parametrize("heads, width, plan", [
    ("gqa-28-over-4x128", 256, (16, 4, 1, 4, 128, 7, 1)),
    ("gqa-28-over-4x128", 64, (16, 4, 1, 4, 128, 7, 1)),
    ("mha-16x128", 256, (16, 1, 16, 1, 128, 1, 16)),
    ("fold2-heads-of-64", 256, (16, 2, 4, 1, 128, 8, 4)),
    ("2-kv-heads-of-256", 256, (16, 2, 1, 2, 256, 8, 1)),
    ("30-heads-in-32-slots", 256, (8, 1, 32, 1, 128, 1, 32)),
    ("30-heads-in-32-slots", 512, (8, 1, 32, 1, 128, 1, 16)),
    ("gqa-28-over-4x128", 512, (16, 4, 1, 4, 128, 7, 0)),
])
def test_the_plan_follows_the_page_and_the_width(heads, width, plan):
    """At the cells' sizes (pages of 32 tokens): a block of 512 tokens, or
    what 8 MiB of slots hold (olmo-hybrid's 256 KB pages: 8); a tile of
    whole kv rows under 10,240 query rows."""
    hq, hkv, d = HEADS[heads]
    assert tuple(paged_prefill.prefill_plan(
        hq, d, _pool(hkv, d), width, 128)) == plan


@pytest.mark.parametrize("over, pool, table, width, mesh_shape, why", [
    ({}, _pool(), (2, 8), 16, None, ""),
    ({}, _pool(dtype=jnp.float32), (2, 8), 8, None, ""),
    (dict(attn_soft_cap=30.0), _pool(), (2, 8), 16, None, ""),
    (dict(sliding_window=64), _pool(), (2, 8), 16, None, ""),
    (dict(decode_kernel=False), _pool(), (2, 8), 16, None,
     "decode_kernel=False"),
    (dict(alibi=True), _pool(), (2, 8), 16, None, "alibi / sink"),
    (dict(attn_sink=True), _pool(), (2, 8), 16, None, "alibi / sink"),
    (dict(attn_chunk=8192), _pool(), (2, 8), 16, None, "chunked attention"),
    ({}, _pool(dtype=jnp.int8), (2, 8), 16, None, "pool stored as int8"),
    ({}, _pool(dtype=jnp.float8_e4m3fn), (2, 8), 16, None,
     "pool stored as float8_e4m3fn"),
    (dict(kv_scale=0.5), _pool(), (2, 8), 16, None,
     "scaled KV quantization"),
    (dict(head_dim=16), _pool(8, 16), (2, 8), 16, None, ""),
    (dict(head_dim=16), _pool(4, 16), (2, 8), 16, None,
     "a kv row of 16 lanes of heads of 16 is not whole vregs"),
    (dict(head_dim=96), _pool(8, 96), (2, 8), 16, None,
     "a kv row of 96 lanes of heads of 96 is not whole vregs"),
    ({}, _pool(), (2, 8), 16, dict(tp=2), "mesh axes wider than one: tp"),
    ({}, _pool(), (32, 16384), 16, None,
     "block table over the SMEM a core can stage"),
    ({}, _pool(), (2, 8), 5, None,
     "5 queries a row are not whole sublanes"),
    ({}, _pool(), (2, 8), 8, None,
     "8 queries a row are not whole sublanes"),
    ({}, _pool(), (2, 8), 512, None,
     "512 queries a row over the kernel's tile of 10240 query rows"),
], ids=["bf16-takes", "float32-takes-8", "soft-cap-takes", "window-takes",
        "decode-kernel-off", "alibi", "sink", "chunked", "int8-pool",
        "fp8-pool", "kv-scale", "heads-of-16-eight-to-a-row",
        "four-heads-of-16-a-head-a-row", "heads-of-96", "tp2", "table",
        "spec-verify-width", "bf16-width-8", "over-the-tile"])
def test_what_the_kernel_declines_and_what_it_takes(
        cpu_devices, over, pool, table, width, mesh_shape, why):
    mesh = contextlib.nullcontext()
    if mesh_shape:
        mesh = jax.sharding.set_mesh(build_mesh(
            MeshConfig(**mesh_shape), cpu_devices[:2]))
    spec = _spec(**over)
    hq = pool.shape[3] * (pool.shape[4] // spec.head_dim) * 7
    q = jax.ShapeDtypeStruct((table[0], width, hq, spec.head_dim),
                             jnp.float32 if pool.dtype == jnp.float32
                             else jnp.bfloat16)
    with mesh:
        assert paged_prefill.declined(
            spec, q, pool, jax.ShapeDtypeStruct(table, jnp.int32)) == why


@pytest.mark.parametrize("decode_kernel, path", [(None, "pallas-interpret"),
                                                 (False, "xla")])
def test_the_call_site_notes_the_plan_or_the_decline(decode_kernel, path):
    """``chunk_attention`` is the call site's whole decision: the kernel's
    result and its plan, or None and why."""
    hq, hkv, d = HEADS["gqa-28-over-4x128"]
    rng = np.random.default_rng(3)
    spec = _spec(decode_kernel=decode_kernel)
    x = _case(rng, [5, 60], 16, hq, hkv, d, jnp.float32)
    notes = set()
    with kernel_mode.recording(notes):
        out = paged_prefill.chunk_attention(
            spec, x["q"], x["k"], x["v"], 1, x["pos"], x["table"],
            jnp.asarray(0, jnp.int32), " window=0")
    if path == "xla":
        assert out is None
        assert notes == {("paged_prefill", "xla",
                          "rows=2 width=16: decode_kernel=False")}
    else:
        assert out.shape == (2, 16, hq, d)
        assert notes == {("paged_prefill", "pallas-interpret",
                          "rows=2 width=16 pages=12 heads=28 fold=4 "
                          "tile=28x16 window=0")}
    assert kernel_mode.paged_prefill_on_kernel(notes) == (path != "xla")


def test_no_note_of_the_kernel_reads_as_the_gathered_form():
    on = kernel_mode.paged_prefill_on_kernel
    assert not on(set())
    assert not on({("paged_prefill", "xla", "x"),
                   ("paged_decode", "pallas", ""),
                   ("mla_prefill", "pallas", "")})
    assert on({("paged_prefill", "pallas", "x")})
    assert not kernel_mode.prefill_attn_on_kernel(
        {("paged_prefill", "pallas", "x")})


# ---------------------------------------------------------------------------
# the call site: a layer_pattern on one pool, a pack of a hybrid stack
# ---------------------------------------------------------------------------

#: gemma2 at a toy size with heads of 128 lanes: alternating window / global
#: layers on ONE pool (the window a traced scalar a layer), a soft cap
GEMMA2 = dict(model_type="gemma2", vocab_size=128, hidden_size=64,
              intermediate_size=96, num_hidden_layers=4,
              num_attention_heads=4, num_key_value_heads=2, head_dim=128,
              sliding_window=16, query_pre_attn_scalar=16,
              attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
              rms_norm_eps=1e-6, rope_theta=10000.0,
              max_position_embeddings=512)


def test_a_layer_pattern_serves_the_gathered_forms_logits(monkeypatch):
    """Through ``PagedEngineAdapter``: a prompt of 70 walked in chunks of 32
    (twice the window), a second row admitted beside a decoding one (the
    two-row pack), decode steps between. With the chunks on the kernel -
    ``window=16 by layer`` in the record, every prefill dispatch counted -
    every position's logits are those of the gathered form
    (``decode_kernel=False``), which counts none."""
    import dataclasses

    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import (
        PagedCausalLMApplication)
    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
    from test_recurrent_paged import LogitTap, _decode
    family = get_family("gemma2")
    rng = np.random.default_rng(49)
    long, short = (rng.integers(1, 128, size=n).tolist() for n in (70, 21))

    def served(kernel):
        tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                         batch_size=2, seq_len=128, pa_block_size=8,
                         pa_num_blocks=64, context_encoding_buckets=[8, 32],
                         enable_bucketing=True, is_block_kv_layout=True,
                         is_prefix_caching=False)
        app = PagedCausalLMApplication(
            None, family.config_cls(tcfg, **GEMMA2), family)
        assert app.spec.layer_pattern == (True, False) * 2
        if not kernel:
            app.spec = dataclasses.replace(app.spec, decode_kernel=False)
        app.init_random_weights(seed=3).init_cache()
        reg = telemetry.MetricsRegistry()
        ad = PagedEngineAdapter(app, telemetry=reg)
        tap = LogitTap(app)
        stream = {7: [ad.add_requests([7], [long])[7]]}
        _decode(ad, [7], stream, 3)
        stream[8] = [ad.add_requests([8], [short])[8]]
        _decode(ad, None, stream, 5)
        chunks = {(k["path"], k["reason"])
                  for k in app.warmup_state()["kernels"]
                  if k["site"] == "paged_prefill"}
        series = {s["labels"]["attn"]: s["value"] for s in reg.snapshot()[
            "metrics"][tmetrics.PREFILL_DISPATCHES_TOTAL]["series"]}
        return (tap.logits(7, 78), tap.logits(8, 26), stream, chunks,
                ad.host_stats, series)
    on, off = served(True), served(False)
    assert {path for path, _ in on[3]} == {"pallas-interpret"}
    assert {why.split(" tile=")[1] for _, why in on[3]} >= {
        "4x32 window=16 by layer", "4x8 window=16 by layer"}
    assert on[4]["prefill_dispatches_paged_attn_kernel"] \
        == on[4]["prefill_dispatches"] >= 4
    assert {path for path, _ in off[3]} == {"xla"}
    assert off[4]["prefill_dispatches_paged_attn_kernel"] == 0
    # the counter's nxdi twin: the dispatches by their chunk attention
    assert on[5] == {"paged": on[4]["prefill_dispatches"]}
    assert off[5] == {"xla": off[4]["prefill_dispatches"]}
    assert on[2] == off[2]
    for got, want in zip(on[:2], off[:2]):
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(got, want, atol=2e-5)


# ---------------------------------------------------------------------------
# the timing script behind the decline rule's table
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_prefill_time():
    spec = importlib.util.spec_from_file_location(
        "paged_prefill_time", REPO / "scripts" / "paged_prefill_time.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_scripts_floor_is_the_issues(paged_prefill_time):
    """3.67 MFLOP a cached token a layer = 0.019 us at 197 TFLOP/s for
    SmallThinker's 28 heads x 256 queries of 128 lanes."""
    hq, _, d, _, _ = paged_prefill_time.CELLS["smallthinker"]
    assert paged_prefill_time.floor_us_a_token(hq, d, 256) == \
        pytest.approx(0.0186, abs=2e-4)


def test_the_scripts_cells_are_the_benchmarks(paged_prefill_time):
    import json
    files = {"smallthinker": "smallthinker-21b-a3b", "olmoe": "olmoe-1b-7b",
             "granite": "granite-4.0-h-micro",
             "olmo-hybrid": "olmo-hybrid-7b",
             "qwen3-next": "qwen3-next-80b-a3b"}
    for cell, (hq, hkv, d, tokens, window) in \
            paged_prefill_time.CELLS.items():
        cfg = json.loads((REPO / "benchmark" / "configs"
                          / f"{files[cell]}.json").read_text())
        heads = cfg["num_attention_heads"]
        assert bkv.pool_kv_heads(cfg["num_key_value_heads"]) == hkv
        assert hq == heads + (hkv - cfg["num_key_value_heads"]) * (
            heads // cfg["num_key_value_heads"])
        assert d == cfg.get("head_dim", cfg["hidden_size"] // heads)
        assert tokens == cfg["serve"]["seq_len"]
        assert window == cfg.get("sliding_window_size", 0)


def test_the_script_prints_no_time_without_a_chip(paged_prefill_time, capsys):
    assert paged_prefill_time.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err
