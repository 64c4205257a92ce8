"""The latent decode kernel's block loop (ISSUE 53, ``ops/mla_decode.py``):
one stream of blocks across the batch's rows - the next row's first block in
flight under this row's last, inner blocks unmasked, the heads or the tokens
held still on the MXU by the head count - in interpret mode on the CPU against
the XLA absorbed form ``model_base._mla_attend``; the engagement record's
text; the timing script's floors and its refusal to print a time without a
chip."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.ops import mla_decode

REPO = Path(__file__).resolve().parent.parent
RANK, ROPE, NOPE, V, LANES, BS = 128, 64, 16, 16, 256, 8
#: a block is 32 pages of 8 tokens (the table of 288 pages allows it; at 64
#: heads a block may hold 1,024 tokens, at 128 heads 512)
COLS = 32 * BS

#: name -> the row's cached length. ALL of them are one batch, in this order:
#: the dead row lies between two live ones, so the row before it hands its
#: slot over to the row after it.
ROWS = {
    "three-blocks-and-a-bit": 3 * COLS + 5,
    "dead-between-two-live": 0,
    "one-short-of-a-page": BS - 1,
    "ends-on-a-block-edge": COLS,
    "one-block": COLS - 29,
    "nine-blocks": 9 * COLS - 3,
}


def _spec():
    return SimpleNamespace(
        mla=SimpleNamespace(kv_lora_rank=RANK, qk_rope_head_dim=ROPE,
                            qk_nope_head_dim=NOPE, v_head_dim=V,
                            latent_dim=RANK + ROPE),
        scale=0.17, kv_scale=None)


@pytest.fixture(scope="module")
def served():
    """heads, dtype -> (the kernel's rows, the XLA absorbed form's), once
    for all the rows of a batch."""
    done = {}

    def run(heads, dtype):
        if (heads, dtype) in done:
            return done[heads, dtype]
        rng = np.random.default_rng(heads)
        lens = np.array(list(ROWS.values()))
        b, mb = len(lens), 9 * 32
        pool = np.zeros((2, 1 + b * mb, BS, 1, LANES), np.float32)
        pool[..., :RANK + ROPE] = rng.normal(
            size=pool.shape[:-1] + (RANK + ROPE,))
        pool[:, 0] = 0
        table = np.stack([1 + r * mb + rng.permutation(mb)
                          for r in range(b)])
        table[lens == 0] = 0                # a dead row: null blocks
        cast = lambda x: jnp.asarray(x, dtype)                   # noqa: E731
        q_nope = cast(rng.normal(size=(b, heads, NOPE)))
        q_rot = cast(rng.normal(size=(b, heads, ROPE)))
        lat_new = cast(rng.normal(size=(b, RANK + ROPE)))
        w_kvb = cast(rng.normal(size=(RANK, heads, NOPE + V)) * 0.1)
        pool, table = cast(pool), jnp.asarray(table, jnp.int32)
        spec = _spec()
        got = mla_decode.mla_decode_attention(
            q_nope, q_rot, lat_new, w_kvb, pool, 1, jnp.asarray(lens), table,
            scale=spec.scale, rank=RANK, interpret=True)
        assert got.shape == (b, heads, V) and got.dtype == dtype
        want = model_base._mla_attend(
            spec, q_nope[:, None], q_rot[:, None], lat_new[:, None], w_kvb,
            pool, 1, table, jnp.asarray(lens)[:, None], True)[:, 0]
        done[heads, dtype] = (np.asarray(got, np.float32),
                              np.asarray(want, np.float32))
        return done[heads, dtype]
    return run


@pytest.mark.parametrize("row", list(ROWS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("heads", [64, 128], ids=["tokens-held-64",
                                                  "heads-held-128"])
def test_the_kernel_agrees_with_the_absorbed_xla_form(served, heads, dtype,
                                                      row):
    """Both roles of the MXU's operands, both pool dtypes, every edge of the
    stream in ONE batch; float32 at the tolerance of
    ``test_longcat_flash_paged``'s kernel case, bf16 at the prefill
    kernel's."""
    got, want = served(heads, dtype)
    r = list(ROWS).index(row)
    np.testing.assert_allclose(
        got[r], want[r], atol=2e-5 if dtype == jnp.float32 else 4e-2)


@pytest.mark.parametrize("heads, tiles, pages", [
    (128, "heads", 16), (64, "tokens", 32), (4, "tokens", 32),
    (256, "heads", 8)])
def test_the_note_names_the_form_the_shape_runs(heads, tiles, pages):
    pool = jnp.zeros((2, 4, 32, 1, 640), jnp.bfloat16)
    assert mla_decode.heads_held(heads) == (tiles == "heads")
    assert mla_decode.plan_note(pool, heads) == (
        f"latent lanes=640 heads={heads} form=absorbed pages={pages} "
        f"tiles={tiles}-held prefetch=across-rows")


# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mla_decode_time():
    spec = importlib.util.spec_from_file_location(
        "mla_decode_time", REPO / "scripts" / "mla_decode_time.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("heads, side, ns", [
    (128, "flop", 1.414), (64, "flop", 0.707), (128, "bytes", 1.563),
    (64, "bytes", 1.563)])
def test_the_scripts_floors_are_the_issues(mla_decode_time, heads, side, ns):
    assert mla_decode_time.floors_ns_a_token(heads)[side] == \
        pytest.approx(ns, abs=2e-3)


@pytest.mark.parametrize("heads, rows, live", [(128, 32, 32), (64, 32, 28)])
def test_the_scripts_rows_are_the_cells(mla_decode_time, heads, rows, live):
    lens = mla_decode_time.row_lengths(heads)
    mean, ctx = mla_decode_time.SHAPES[heads][2:]
    assert len(lens) == rows and int((lens > 0).sum()) == live
    assert lens.max() < ctx and lens[lens > 0].min() >= 700
    assert lens.sum() / live == pytest.approx(mean, rel=0.15)


def test_the_script_prints_no_time_without_a_chip(mla_decode_time, capsys):
    assert mla_decode_time.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err
