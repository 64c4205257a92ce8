"""The selection kernel (ISSUE 51, ``ops/index_select.py``): a step's or a
chunk's index queries over the row's LIVE index pages, in interpret mode on
the CPU against the form ``model_base._indexer_block`` keeps for declines
(``model_base._gathered_select``: ``gather_index_rows`` ->
``_index_scores`` -> ``topk_select``): the same
set, bit for bit, on float32 pools - fold 2 (the served page: two keys of 64
to a row) and fold 1, widths 1, 8 and 64, rows of every length, a scrambled
table whose entries past the live pages name a poisoned null page, ties at
the threshold, a chunk whose own keys were written in the same step - and on
bfloat16 wherever the oracle's margin at the threshold is not rounding; every
branch of ``declined``; the engagement record's text; the call site inside
``_indexer_block``; the timing script's refusal to print a time without a
chip."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
from neuronx_distributed_inference_tpu.ops import index_select, kernel_mode
from neuronx_distributed_inference_tpu.ops.rope import RopeConfig
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh)

REPO = Path(__file__).resolve().parent.parent
BS, MB, HEADS, K = 32, 8, 4, 32
TABLE = MB * BS


def _case(rng, lasts, t, dim, dtype=jnp.float32, whole=False, layers=2):
    """Rows whose LAST query stands at ``lasts`` (``t`` consecutive
    positions a row; a position under 0 is a pad query that sees nothing)
    over an index-key pool of scattered pages, stored as the application
    stores it (``bkv.index_page``). Block 0 is the null block and holds NaN;
    every table entry past a row's last live page names it. ``whole``:
    small whole numbers, so every float32 sum is exact whatever its order
    and scores tie in crowds (a zero wherever every head's ReLU is shut)."""
    b = len(lasts)
    prow, lanes = bkv.index_page(dim, BS)

    def draw(*shape):
        return (rng.integers(-2, 3, size=shape) if whole
                else rng.normal(size=shape)).astype(np.float32)
    pool = draw(layers, 1 + b * MB, prow, lanes)
    pool[:, 0] = np.nan
    table = np.stack([1 + r * MB + rng.permutation(MB) for r in range(b)])
    for r, last in enumerate(lasts):
        table[r, max(last, 0) // BS + 1:] = 0
    pos = np.stack([np.arange(last - t + 1, last + 1) for last in lasts])
    return dict(qi=jnp.asarray(draw(b, t, HEADS, dim), dtype),
                w=jnp.asarray(draw(b, t, HEADS), dtype),
                pool=jnp.asarray(pool, dtype),
                table=jnp.asarray(table, jnp.int32),
                pos=jnp.asarray(pos, jnp.int32))


def _scores(c, layer, k=K):
    """``_index_scores`` of the case over the gathered table (the null
    page's NaN taken out: the oracle reads it, under its mask) and the
    causal mask."""
    dim = c["qi"].shape[-1]
    rows = bkv.gather_index_rows(c["pool"], layer, c["table"])
    scores = model_base._index_scores(
        SimpleNamespace(index_heads=HEADS, index_dim=dim, topk=k),
        c["qi"], c["w"], jnp.where(jnp.isnan(rows), 0, rows))
    seen = jnp.arange(TABLE)[None, None, :] <= c["pos"][:, :, None]
    return scores, seen


def _oracle(c, layer, k=K):
    """The form ``_indexer_block`` keeps for declines, over the same pool
    (less the null page's NaN, which it reads under its mask)."""
    sp = SimpleNamespace(index_heads=HEADS, index_dim=c["qi"].shape[-1],
                         topk=k)
    return np.asarray(model_base._gathered_select(
        sp, c["qi"], c["w"], jnp.where(jnp.isnan(c["pool"]), 0, c["pool"]),
        layer, c["pos"], c["table"]))


def _kernel(c, layer, k=K):
    return np.asarray(index_select.index_select(
        c["qi"], c["w"], c["pool"], layer, c["pos"], c["table"], topk=k,
        interpret=True))


#: the last position of each row: one token, under k, exactly k seen, one
#: over, a page's edge, well over, the table's full width
LASTS = [0, 17, K - 1, K, 63, 64, 130, TABLE - 1]


@pytest.mark.parametrize("whole", [False, True], ids=["normal", "whole"])
@pytest.mark.parametrize("dim", [64, 128], ids=["fold2", "fold1"])
@pytest.mark.parametrize("t", [1, 8, 64])
def test_the_selection_is_the_oracles_bit_for_bit(rng, t, dim, whole):
    c = _case(rng, [last for last in LASTS if last >= t - 1] + [TABLE - 1],
              t, dim, whole=whole)
    want, got = _oracle(c, 1), _kernel(c, 1)
    assert got.shape == want.shape == (len(c["pos"]), t, TABLE)
    assert (got == want).all()
    # what it is: each query keeps min(seen, k) tokens, none past itself
    assert (got.sum(-1) == np.minimum(np.asarray(c["pos"]) + 1, K)).all()


@pytest.mark.parametrize("t", [1, 8])
def test_ties_at_the_threshold_go_to_the_lower_position(rng, t):
    """Every head's ReLU shut (queries against keys of the other sign):
    every score an exact zero, so the k lowest positions are kept; then a
    few keys that score above zero, and the zeros fill what room is left
    from the lowest position."""
    c = _case(rng, [200, TABLE - 1], t, 64, whole=True)
    c["qi"] = jnp.abs(c["qi"]) + 1
    c["w"] = jnp.abs(c["w"]) + 1
    pool = -jnp.abs(jnp.where(jnp.isnan(c["pool"]), 0, c["pool"])) - 1
    c["pool"] = pool.at[:, 0].set(jnp.nan)
    got = _kernel(c, 0)
    assert (got == _oracle(c, 0)).all()
    assert (got[:, :, :K]).all() and not got[:, :, K:].any()
    # seven tokens of row 0 (positions 40, 50, .., 100) score above zero
    prow = bkv.index_page(64, BS)[0]
    for p in range(40, 101, 10):
        page, o = int(c["table"][0, p // BS]), p % BS
        at = (slice(None), page, o % prow,
              slice(o // prow * 64, (o // prow + 1) * 64))
        c["pool"] = c["pool"].at[at].set(-c["pool"][at])
    got = _kernel(c, 0)
    assert (got == _oracle(c, 0)).all()
    assert got[0, :, 40:101:10].all() and got[0, :, :K - 7].all()
    assert not got[0, :, K - 7:40].any()


def test_a_row_of_no_position_selects_nothing(rng):
    """A pad query (position -1) and a row whose queries are all pads."""
    c = _case(rng, [3, -1, 100], 8, 64)
    got = _kernel(c, 1)
    assert (got == _oracle(c, 1)).all()
    assert not got[1].any() and not got[0, :4].any() and got[0, 4:].any()


def test_pages_past_the_live_ones_are_never_read(rng):
    """The poisoned null page behind every entry past a row's last live page
    (and the table's order scrambled): a NaN read would sit in a score and
    the order keys of a NaN are no float's."""
    c = _case(rng, [40, 130], 8, 64)
    assert np.isnan(np.asarray(c["pool"][1, 0])).all()
    assert (np.asarray(c["table"])[0, 2:] == 0).all()
    got = _kernel(c, 1)
    assert (got == _oracle(c, 1)).all()
    # the live pages DO matter: another layer's keys, another selection
    assert (_kernel(c, 0) != got).any()


@pytest.mark.parametrize("t", [1, 8])
def test_a_chunks_own_keys_written_in_the_same_step(rng, t):
    """``write_index_keys`` then the kernel, as ``_indexer_block`` runs
    them: the chunk's own keys are read from the pool."""
    c = _case(rng, [99, 215], t, 64)
    # keys that their own query scores far above the rest: each is among
    # its query's k only if the kernel reads what the step wrote
    c["w"] = jnp.abs(c["w"]) + 0.1
    new = 100 * c["qi"][:, :, 0]
    slots = (jnp.take_along_axis(c["table"], c["pos"] // BS, axis=1) * BS
             + c["pos"] % BS)
    own = np.asarray(c["pos"])[:, :, None] == np.arange(TABLE)
    assert not _kernel(c, 1)[own].all()
    c["pool"] = bkv.write_index_keys(c["pool"], new, 1, slots, c["pos"], BS)
    got = _kernel(c, 1)
    assert (got == _oracle(c, 1)).all()
    assert got[own].all()


@pytest.mark.parametrize("t", [1, 64])
def test_a_bfloat16_pool_selects_alike_where_the_margin_is_not_rounding(
        rng, t):
    """bfloat16 operands: the products are exact in float32 and the sums'
    order is the matmul's, so a score may differ in its last bits; wherever
    the oracle's k-th and (k + 1)-th largest lie over 1e-5 apart
    (relative) the selection is the oracle's."""
    c = _case(rng, [63, 130, TABLE - 1], t, 64, dtype=jnp.bfloat16)
    scores, seen = _scores(c, 1)
    want, got = _oracle(c, 1), _kernel(c, 1)
    ranked = np.sort(np.where(np.asarray(seen), np.asarray(scores), -np.inf),
                     axis=-1)[..., ::-1]
    kth, nxt = ranked[..., K - 1], ranked[..., K]
    with np.errstate(invalid="ignore"):     # -inf less -inf: under k seen
        clear = ~np.isfinite(nxt) | (kth - nxt > 1e-5 * np.abs(kth))
    assert clear.mean() > 0.9
    assert (got == want)[clear].all()
    assert (got.sum(-1) == want.sum(-1)).all()


def _spec(**over):
    return SimpleNamespace(**{**dict(
        decode_kernel=None,
        sparse=model_base.SparseSpec(
            index_heads=HEADS, index_dim=64, topk=K,
            rope=RopeConfig(head_dim=64))), **over})


def _call(t=8, dim=64, dtype=jnp.bfloat16, rows=2, mb=MB):
    prow, lanes = bkv.index_page(dim, BS)
    return (jnp.zeros((rows, t, HEADS, dim), dtype),
            jnp.zeros((2, 1 + rows * mb, prow, lanes), dtype),
            jnp.zeros((rows, mb), jnp.int32))


DECLINES = {
    "decode_kernel=False": lambda: (_spec(decode_kernel=False), *_call()),
    "pool stored as float16": lambda: (
        _spec(), _call()[0], _call(dtype=jnp.float16)[1], _call()[2]),
    "is not whole vregs": lambda: (
        _spec(), _call(dim=48)[0], _call(dim=48)[1][..., :96], _call()[2]),
    "block table over the SMEM": lambda: (
        _spec(), *_call(rows=64)[:2], jnp.zeros((64, 4096), jnp.int32)),
    "5 queries a row are not whole sublanes": lambda: (_spec(), *_call(t=5)),
    "one query a row: the gathered form is ahead by the clock": lambda: (
        _spec(), *_call(t=1)),
    "bytes of VMEM": lambda: (
        _spec(), *_call()[:2], jnp.zeros((2, 30000), jnp.int32)),
}


@pytest.mark.parametrize("why", DECLINES)
def test_declined_names_what_the_call_shows(why):
    assert why in index_select.declined(*DECLINES[why]())


def test_declined_names_a_mesh_wider_than_one(cpu_devices):
    with jax.sharding.set_mesh(build_mesh(MeshConfig(tp=2),
                                          cpu_devices[:2])):
        assert "mesh axes wider than one: tp" in index_select.declined(
            _spec(), *_call())
    assert index_select.declined(_spec(), *_call()) == ""
    assert index_select.declined(_spec(), *_call(t=256, rows=32)) == ""
    assert index_select.declined(_spec(), *_call(dtype=jnp.float32)) == ""


@pytest.mark.parametrize("t,rows,mb,tile", [
    (1, 32, 384, "1x16"), (64, 1, 384, "64x16"), (256, 32, 384, "256x16"),
    (8, 2, 4, "8x4")])
def test_the_plan_and_its_note(t, rows, mb, tile):
    """The engagement record's text at the served shapes: the tile follows
    the width, the block the table."""
    _, pool, _ = _call()
    plan = index_select.select_plan(64, pool, t, mb)
    assert plan.fold == 2 and plan.tile == t
    assert plan.band == (1 if t == 1 else min(t, 32))
    assert plan.note(rows, t, 16, 64, 2048) == (
        f"rows={rows} width={t} pages={min(16, mb)} heads=16x64 fold=2 "
        f"topk=2048 tile={tile}")
    # a table whose keys outgrow VMEM at 256 queries takes a narrower tile
    assert index_select.select_plan(64, pool, 256, 1024).tile == 128


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas", "xla"])
def test_the_indexer_block_takes_the_kernel_where_it_is_not_declined(
        rng, kernel):
    """``_indexer_block`` end to end (projections, the key's norm, rotary,
    the write, the selection) with and without the kernel: the same pool,
    the same selection, and the record of which form ran."""
    hidden, t, dim = 32, 8, 64
    c = _case(rng, [99, 215], t, dim)
    spec = SimpleNamespace(decode_kernel=None if kernel else False,
                           sparse=_spec().sparse)
    sp = spec.sparse
    layer_w = {
        "idx_proj": jnp.asarray(
            rng.normal(size=(hidden, sp.proj_width)) * 0.2, jnp.float32),
        "idx_k_norm": jnp.ones((dim,), jnp.float32),
        "idx_k_norm_b": jnp.zeros((dim,), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(2, t, hidden)), jnp.float32)
    cos, sin = model_base.rope_cos_sin(c["pos"], sp.rope)
    ai = {"cos_i": cos, "sin_i": sin}
    slots = (jnp.take_along_axis(c["table"], c["pos"] // BS, axis=1) * BS
             + c["pos"] % BS)
    pool = jnp.where(jnp.isnan(c["pool"]), 0, c["pool"])
    notes = set()
    with kernel_mode.recording(notes):
        got, pool_out = model_base._indexer_block(
            spec, h, layer_w, pool, 1, ai, c["pos"], slots, c["table"])
    spec_off = SimpleNamespace(decode_kernel=False, sparse=sp)
    want, pool_want = model_base._indexer_block(
        spec_off, h, layer_w, pool, 1, ai, c["pos"], slots, c["table"])
    assert (np.asarray(got) == np.asarray(want)).all()
    assert (np.asarray(pool_out) == np.asarray(pool_want)).all()
    site, path, reason = next(n for n in notes if n[0] == "index_select")
    assert kernel_mode.select_on_kernel(notes) is kernel
    if kernel:
        assert path == "pallas-interpret" and reason == (
            "rows=2 width=8 pages=8 heads=4x64 fold=2 topk=32 tile=8x8")
    else:
        assert (path, reason) == ("xla", "rows=2 width=8: decode_kernel=False")


def test_what_the_adapter_reads_from_a_programs_notes():
    on = kernel_mode.select_on_kernel
    assert on({("index_select", "pallas", "rows=1 ...")}) is True
    assert on({("index_select", "xla", "rows=1: why")}) is False
    assert on({("paged_prefill", "pallas", "")}) is False
    assert on(frozenset()) is False


@pytest.fixture(scope="module")
def index_select_time():
    spec = importlib.util.spec_from_file_location(
        "index_select_time", REPO / "scripts" / "index_select_time.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_script_prints_no_time_without_a_chip(index_select_time, capsys):
    assert index_select_time.main([]) == 2
    assert "no TPU" in capsys.readouterr().err
    # what a selection has to read: the rows' live index keys, once
    assert index_select_time.live_bytes(32, 6144, 1) == 32 * 6145 * 128
