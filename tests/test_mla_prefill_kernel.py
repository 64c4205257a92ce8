"""The latent prefill kernel (ISSUE 48, ``ops/mla_prefill.py``): a chunk of
T > 1 queries a row over the row's latent pages, its own rows among them, in
interpret mode on the CPU against the XLA form ``model_base._mla_attend``
(both of its forks); every branch of ``declined``; the engagement record's
text and what the adapter's counter reads from it; the timing script's floors
and its refusal to print a time without a chip."""

import contextlib
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.ops import kernel_mode, mla_prefill
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh)

REPO = Path(__file__).resolve().parent.parent
RANK, ROPE, NOPE, V, LANES, BS = 128, 64, 128, 128, 256, 8


def _spec(heads=4, **over):
    return SimpleNamespace(**{**dict(
        mla=SimpleNamespace(kv_lora_rank=RANK, qk_rope_head_dim=ROPE,
                            qk_nope_head_dim=NOPE, v_head_dim=V,
                            latent_dim=RANK + ROPE),
        gqa=SimpleNamespace(num_q_heads=heads), scale=0.17, kv_scale=None,
        attn_soft_cap=None, attn_sink=False, alibi=False, sliding_window=0,
        decode_kernel=None), **over})


def _case(rng, firsts, t, heads, dtype, mb=80):
    """Rows of ``t`` queries at ``firsts``; a pool of scattered pages whose
    rows at the chunk's own positions are the chunk's latents (the caller
    writes them before the kernel runs), block 0 the null block."""
    b = len(firsts)
    pool = np.zeros((3, 1 + b * mb, BS, 1, LANES), np.float32)
    pool[..., :RANK + ROPE] = rng.normal(
        size=pool.shape[:-1] + (RANK + ROPE,))
    pool[:, 0] = 0
    table = np.stack([1 + r * mb + rng.permutation(mb) for r in range(b)])
    pos = np.asarray(firsts)[:, None] + np.arange(t)[None]
    flat = pool[1].reshape(-1, LANES)
    lat_new = np.stack([
        flat[table[r][np.minimum(pos[r] // BS, mb - 1)] * BS
             + pos[r] % BS][:, :RANK + ROPE]
        for r in range(b)])
    cast = lambda x: jnp.asarray(x, dtype)                       # noqa: E731
    return dict(
        q_nope=cast(rng.normal(size=(b, t, heads, NOPE))),
        q_rot=cast(rng.normal(size=(b, t, heads, ROPE))),
        w_kvb=cast(rng.normal(size=(RANK, heads, NOPE + V)) * 0.1),
        pool=cast(pool), lat_new=cast(lat_new),
        table=jnp.asarray(table, jnp.int32),
        pos=jnp.asarray(pos, jnp.int32))


#: name -> each row's first position. A block is 16 pages of 8 tokens = 128.
PREFIXES = {
    "head-of-prompt": [0, 0],
    "one-token": [1, 1],
    "mid-page-not-a-block": [13, 77],
    "several-blocks": [300, 515],
    "rows-differ": [0, 389],
    "a-block-exactly": [128, 256],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("heads", [4, 8], ids=["heads4-for-64",
                                               "heads8-for-128"])
@pytest.mark.parametrize("prefix", sorted(PREFIXES))
def test_the_kernel_agrees_with_the_xla_form(prefix, heads, dtype):
    """One tile of whole heads at 4 heads x 16 queries, two at 8 (the tile
    is shrunk so that the grid over query tiles is walked)."""
    rng = np.random.default_rng(len(prefix) * heads)
    spec = _spec(heads)
    t = 16
    x = _case(rng, PREFIXES[prefix], t, heads, dtype)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mla_prefill, "MLA_PREFILL_TILE_ROWS", 4 * t)
        assert mla_prefill.tile_heads(heads, t) == 4
        got = mla_prefill.mla_prefill_attention(
            x["q_nope"], x["q_rot"], x["w_kvb"], x["pool"], 1,
            x["pos"][:, 0], x["table"], scale=spec.scale, rank=RANK,
            interpret=True)
    assert got.shape == (2, t, heads, V) and got.dtype == dtype
    for absorbed in (True, False):
        want = model_base._mla_attend(
            spec, x["q_nope"], x["q_rot"], x["lat_new"], x["w_kvb"],
            x["pool"], 1, x["table"], x["pos"], absorbed)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=2e-5 if dtype == jnp.float32 else 4e-2,
            err_msg=f"absorbed={absorbed}")


def test_a_pad_query_past_the_table_reads_inside_it():
    """A chunk whose pad positions run past the table's last page (the
    adapter gives pad columns ``first + t`` whatever the table holds): the
    real queries agree with the XLA form, every value is finite."""
    rng = np.random.default_rng(7)
    spec = _spec(4)
    t, mb = 16, 4                                  # the table holds 32 tokens
    x = _case(rng, [20, 3], t, 4, jnp.float32, mb=mb)
    got = mla_prefill.mla_prefill_attention(
        x["q_nope"], x["q_rot"], x["w_kvb"], x["pool"], 1, x["pos"][:, 0],
        x["table"], scale=spec.scale, rank=RANK, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    want = model_base._mla_attend(
        spec, x["q_nope"][1:], x["q_rot"][1:], x["lat_new"][1:], x["w_kvb"],
        x["pool"], 1, x["table"][1:], x["pos"][1:], True)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[0]),
                               atol=2e-5)
    # row 0: positions 20..31 are in the table, 32..35 are pad
    want0 = model_base._mla_attend(
        spec, x["q_nope"][:1, :12], x["q_rot"][:1, :12],
        x["lat_new"][:1, :12], x["w_kvb"], x["pool"], 1, x["table"][:1],
        x["pos"][:1, :12], True)
    np.testing.assert_allclose(np.asarray(got[0, :12]), np.asarray(want0[0]),
                               atol=2e-5)


@pytest.mark.parametrize("heads, width, tile", [
    (128, 256, 8), (64, 256, 8), (128, 64, 8), (64, 64, 8), (128, 512, 4),
    (128, 1024, 2), (128, 2048, 1), (128, 4096, 0), (4, 16, 4), (6, 400, 3)])
def test_a_tile_is_whole_heads_under_the_row_limit(heads, width, tile):
    assert mla_prefill.tile_heads(heads, width) == tile


def _pool(dtype=jnp.bfloat16, lanes=LANES):
    return jax.ShapeDtypeStruct((3, 9, BS, 1, lanes), dtype)


@pytest.mark.parametrize("over, pool, table, width, mesh_shape, why", [
    ({}, _pool(), (2, 8), 16, None, ""),
    ({}, _pool(jnp.float32), (2, 8), 16, None, ""),
    ({}, _pool(jnp.int8), (2, 8), 16, None, "latent pool stored as int8"),
    (dict(kv_scale=0.5), _pool(), (2, 8), 16, None,
     "scaled KV quantization"),
    ({}, _pool(lanes=192), (2, 8), 16, None,
     "latent rows or rank not whole vregs"),
    (dict(mla=SimpleNamespace(kv_lora_rank=96, qk_nope_head_dim=128,
                              v_head_dim=128)), _pool(), (2, 8), 16, None,
     "latent rows or rank not whole vregs"),
    (dict(mla=SimpleNamespace(kv_lora_rank=128, qk_nope_head_dim=16,
                              v_head_dim=128)), _pool(), (2, 8), 16, None,
     "a head's nope or value lanes not whole vregs"),
    (dict(mla=SimpleNamespace(kv_lora_rank=128, qk_nope_head_dim=128,
                              v_head_dim=64)), _pool(), (2, 8), 16, None,
     "a head's nope or value lanes not whole vregs"),
    (dict(attn_soft_cap=30.0), _pool(), (2, 8), 16, None,
     "soft cap / sink / alibi / window"),
    (dict(attn_sink=True), _pool(), (2, 8), 16, None,
     "soft cap / sink / alibi / window"),
    (dict(sliding_window=64), _pool(), (2, 8), 16, None,
     "soft cap / sink / alibi / window"),
    ({}, _pool(), (2, 8), 16, dict(tp=2), "mesh axes wider than one: tp"),
    ({}, _pool(), (32, 16384), 16, None,
     "block table over the SMEM a core can stage"),
    ({}, _pool(), (2, 8), 4096, None,
     "4096 queries a row over the kernel's tile of 2048 query rows"),
    ({}, _pool(), (2, 8), 24, None,
     "24 queries a row are not whole sublanes"),
    (dict(gqa=SimpleNamespace(num_q_heads=64)), _pool(), (2, 8), 256, None,
     ""),
    (dict(gqa=SimpleNamespace(num_q_heads=128)), _pool(), (2, 8), 256, None,
     ""),
    (dict(gqa=SimpleNamespace(num_q_heads=64)), _pool(), (2, 8), 512, None,
     ""),
    (dict(gqa=SimpleNamespace(num_q_heads=64)), _pool(), (2, 8), 64, None,
     ""),
], ids=["bf16-takes", "float32-takes", "int8-pool", "kv-scale", "lanes",
        "rank", "nope", "v", "soft-cap", "sink", "window", "tp2", "table",
        "width", "sublanes", "64x256-takes", "128x256-takes",
        "64x512-takes", "64x64-takes"])
def test_what_the_kernel_declines_and_what_it_takes(
        cpu_devices, over, pool, table, width, mesh_shape, why):
    mesh = contextlib.nullcontext()
    if mesh_shape:
        mesh = jax.sharding.set_mesh(build_mesh(
            MeshConfig(**mesh_shape), cpu_devices[:2]))
    with mesh:
        assert mla_prefill.declined(
            _spec(**over), pool, jax.ShapeDtypeStruct(table, jnp.int32),
            width) == why


@pytest.mark.parametrize("decode_kernel, path", [(None, "pallas-interpret"),
                                                 (False, "xla")])
def test_the_call_site_notes_the_plan_or_the_decline(decode_kernel, path):
    """``chunk_attention`` is the call site's whole decision: the kernel's
    result and its plan, or None and the XLA form's text + why."""
    rng = np.random.default_rng(3)
    spec = _spec(4, decode_kernel=decode_kernel)
    x = _case(rng, [5, 140], 16, 4, jnp.float32)
    notes = set()
    with kernel_mode.recording(notes):
        out = mla_prefill.chunk_attention(
            spec, x["q_nope"], x["q_rot"], x["w_kvb"], x["pool"], 1,
            x["pos"], x["table"], "rows=2 width=16 prefix=absorbed")
    if path == "xla":
        assert out is None
        assert notes == {("mla_prefill", "xla", "rows=2 width=16 "
                          "prefix=absorbed (decode_kernel=False)")}
    else:
        assert out.shape == (2, 16, 4, V)
        assert notes == {(
            "mla_prefill", "pallas-interpret",
            "rows=2 width=16 latent lanes=256 heads=4 form=absorbed "
            "tile=4x16 pages=16 folds and own tokens inside")}
    assert kernel_mode.prefill_attn_on_kernel(notes) == (path != "xla")


def test_a_pack_of_rows_is_the_kernels_grid(monkeypatch):
    """Nothing of ``heads x T`` a row leaves the kernel but the result, so a
    full-batch pack needs no row groups under any score budget: one call,
    all rows, each row what it is alone."""
    rng = np.random.default_rng(11)
    spec = _spec(4)
    x = _case(rng, [0, 9, 130, 260], 16, 4, jnp.float32)
    monkeypatch.setattr(model_base, "_paged_score_budget", lambda: 1)
    seen = []
    real = mla_prefill.mla_prefill_attention
    monkeypatch.setattr(
        mla_prefill, "mla_prefill_attention",
        lambda *a, **kw: seen.append(a[0].shape[0]) or real(*a, **kw))
    pack = mla_prefill.chunk_attention(
        spec, x["q_nope"], x["q_rot"], x["w_kvb"], x["pool"], 1, x["pos"],
        x["table"], "")
    assert seen == [4]
    for r in range(4):
        alone = real(x["q_nope"][r:r + 1], x["q_rot"][r:r + 1], x["w_kvb"],
                     x["pool"], 1, x["pos"][r:r + 1, 0], x["table"][r:r + 1],
                     scale=spec.scale, rank=RANK, interpret=True)
        np.testing.assert_allclose(np.asarray(pack[r]), np.asarray(alone[0]),
                                   atol=1e-5)


def test_no_note_of_a_prefill_kernel_reads_as_the_xla_form():
    on = kernel_mode.prefill_attn_on_kernel
    assert not on(set())
    assert not on({("mla_prefill", "xla", "x"), ("mla_decode", "pallas", "")})
    assert on({("mla_prefill", "pallas", "x")})


# ---------------------------------------------------------------------------
# the timing script behind the form's choice
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mla_prefill_time():
    spec = importlib.util.spec_from_file_location(
        "mla_prefill_time", REPO / "scripts" / "mla_prefill_time.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("heads, form, us", [
    (128, "absorbed", 0.362), (128, "expanded", 0.277),
    (64, "absorbed", 0.181), (64, "expanded", 0.138)])
def test_the_scripts_floors_are_the_issues(mla_prefill_time, heads, form, us):
    assert mla_prefill_time.floor_us_a_token(heads, 256, form) == \
        pytest.approx(us, abs=6e-4)


def test_the_script_prints_no_time_without_a_chip(mla_prefill_time, capsys):
    assert mla_prefill_time.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err
