"""The paged decode kernel walks a call's rows as ONE stream of copies (ISSUE
66, ``ops/decode_attention.py``): under a row's last block the kernel starts
the next LIVE row's first block, the two slots, their semaphores and the count
of blocks walked carry across grid steps, a row of length 0 neither starts nor
awaits a copy, and only the call's first live row starts its own first block.

What a stream can get wrong is WHICH row's pages land in WHICH slot, and when:
every case here runs both score forms (block-diagonal, kv row by kv row) in
the Pallas interpreter against ``tests/test_decode_attention.py``'s float32
oracle (the gathered table, the decode mask, ``ops/attention.mha``) at that
file's tolerance, with physical pages shuffled, rows of odd and even block
counts (so that a row's first block lands in either slot), and every page
that no live entry names poisoned with NaN."""

import numpy as np
import jax.numpy as jnp
import pytest

from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
from neuronx_distributed_inference_tpu.modules import kv_cache as kv
from neuronx_distributed_inference_tpu.ops import attention as attn_ops
from neuronx_distributed_inference_tpu.ops import decode_attention as da

from test_decode_attention import _paged_reference, _paged_setup, _rand

#: form -> (query heads, kv heads, head_dim, tokens a page, table width): 16
#: kv rows a token under one query head each in blocks of 4 pages (the
#: block-diagonal form at OLMoE's geometry); 8 kv rows under 8 query heads
#: each in blocks of 16 pages (the kv-row form, as command-a-plus's)
_FORMS = {"mxu-blockdiag": (16, 16, 128, 32, 13),
          "mxu-kv-rows": (64, 8, 128, 8, 52)}
#: tokens of one compute block, in both forms
_BLOCK = 128

#: case -> the rows' prior lengths (0: nothing cached)
_LENS = {
    # 3, 1, 2, 1 and 3 blocks: a row's first block lands in slot 0 or 1
    "mixed": [300, 5, 129, 77, 259],
    "zero_first": [0, 200, 50],
    "zero_middle": [200, 0, 50],
    "zero_last": [200, 50, 0],
    "zero_two_in_a_row": [130, 0, 0, 70],
    "zero_first_and_last": [0, 0, 140, 0],
    "all_empty": [0, 0, 0],
    "one_row": [260],
    "one_block_exactly": [_BLOCK, 40, _BLOCK, _BLOCK],
    "whole_blocks": [3 * _BLOCK, 17, 2 * _BLOCK, 9],
    "window_mid_table": [300, 90, 0, 210, 101],
    "select": [333, 0, 75, 130],
    "sink": [200, 0, 129],
    "soft_cap": [257, 64],
    "kv_scale": [70, 0, 300],
    "bf16": [300, 0, 129, 77],
}


def _plan(form, dtype):
    hq, hkv, d, bs, mb = _FORMS[form]
    plan = da.paged_block_plan(bs, hkv, hq // hkv, d, dtype, mb)
    assert plan.form == form and plan.pages * bs == _BLOCK
    assert plan.note(True).endswith(" prefetch=across-rows")


def _poison(rng, pools, table, lens, bs):
    """Every page that no LIVE table entry names (the null page among them)
    holds NaN, and every dead table entry names such a page."""
    table = table.copy()
    live = np.zeros(pools[0].shape[1], bool)
    for i, n in enumerate(lens):
        live[table[i, :-(-int(n) // bs)]] = True
    dead = np.flatnonzero(~live)
    for i, n in enumerate(lens):
        at = -(-int(n) // bs)
        table[i, at:] = rng.choice(dead, table.shape[1] - at)
    return [jnp.where(~live[None, :, None, None, None], jnp.nan, x)
            for x in pools], table


@pytest.mark.parametrize("case", sorted(_LENS))
@pytest.mark.parametrize("form", sorted(_FORMS))
def test_the_rows_of_a_call_are_one_stream(rng, form, case):
    hq, hkv, d, bs, mb = _FORMS[form]
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    _plan(form, dtype)
    lens = np.array(_LENS[case], np.int32)
    b, scale = len(lens), d ** -0.5
    q, kp, vp, nk, nv, table = (
        x.astype(dtype) if isinstance(x, jnp.ndarray) else x
        for x in _paged_setup(rng, b, hq, hkv, d, bs, mb, lens,
                              num_blocks=1 + 2 * b * mb))
    window = 100 if case == "window_mid_table" else 0
    sink = _rand(rng, hq) if case == "sink" else None
    soft_cap = 30.0 if case == "soft_cap" else None
    kv_scale = 0.25 if case == "kv_scale" else None
    select = None
    if case == "select":
        select = rng.random((b, mb * bs)) < 0.3
        select[np.arange(b), lens] = [True, True, False, True]
    kp_in, vp_in = kp, vp
    if kv_scale is not None:
        # the pools hold x / kv_scale in fp8 (the block-diagonal form) or
        # bf16 (a kv row is read out of a bf16 or 32-bit slot)
        stored = jnp.float8_e4m3fn if form == "mxu-blockdiag" else jnp.bfloat16
        kp_in, vp_in = (kv.quantize_kv(x, stored, kv_scale) for x in (kp, vp))
        kp, vp = (kv.dequantize_kv(x, jnp.float32, kv_scale)
                  for x in (kp_in, vp_in))

    def run(rows, kp_, vp_, table_):
        return da.paged_decode_attention(
            q[rows], kp_, vp_, nk[rows], nv[rows], jnp.asarray(0, jnp.int32),
            jnp.asarray(lens[rows]), jnp.asarray(table_[rows]), scale=scale,
            window=jnp.asarray(window, jnp.int32), sink=sink,
            soft_cap=soft_cap, kv_scale=kv_scale,
            select=None if select is None else jnp.asarray(select[rows]),
            interpret=True)

    every = np.arange(b)
    (kp_nan, vp_nan), wide = _poison(rng, (kp_in, vp_in), table, lens, bs)
    got = run(every, kp_nan, vp_nan, wide)
    assert got.dtype == dtype and np.isfinite(np.asarray(got, np.float32)).all()
    f32 = [jnp.asarray(np.asarray(x, np.float32)) for x in (q, kp, vp, nk, nv)]
    if select is None:
        want = _paged_reference(*f32, lens, table, scale, window=window,
                                sink=sink, soft_cap=soft_cap)
    else:
        k_all = np.array(bkv.gather_block_kv(f32[1][0], jnp.asarray(table)))
        v_all = np.array(bkv.gather_block_kv(f32[2][0], jnp.asarray(table)))
        k_all[every, lens], v_all[every, lens] = f32[3], f32[4]
        mask = (np.arange(mb * bs)[None] <= lens[:, None]) & select
        want = attn_ops.mha(f32[0][:, None], jnp.asarray(k_all),
                            jnp.asarray(v_all), jnp.asarray(mask)[:, None, :],
                            scale)[:, 0]
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)
    # the order in which a row's blocks are scored is its own: the row alone
    # in a call (its first block in slot 0, started by itself) gives the
    # row's result in the batch, bit for bit
    alone = int(np.argmax(lens))
    np.testing.assert_array_equal(
        np.asarray(run(every[alone:alone + 1], kp_in, vp_in, table)[0]),
        np.asarray(got[alone]))


@pytest.mark.parametrize("form", sorted(_FORMS))
def test_a_call_reads_a_page_once_whatever_its_neighbours(rng, form):
    """The stream hands the slots from row to row: the same rows in another
    order, and with empty rows between them, give each row the same bits."""
    hq, hkv, d, bs, mb = _FORMS[form]
    lens = np.array([300, 5, 129, 77, 259], np.int32)
    b = len(lens)
    q, kp, vp, nk, nv, table = _paged_setup(rng, b, hq, hkv, d, bs, mb, lens)

    def run(order):
        keep = np.maximum(order, 0)
        return np.asarray(da.paged_decode_attention(
            q[keep], kp, vp, nk[keep], nv[keep], jnp.asarray(0, jnp.int32),
            jnp.asarray(np.where(order < 0, 0, lens[keep])),
            jnp.asarray(table[keep]), scale=d ** -0.5, interpret=True))

    base = run(np.arange(b))
    order = np.array([3, -1, 0, 4, -1, -1, 2, 1])       # -1: an empty row
    got = run(order)
    for at, row in enumerate(order):
        if row >= 0:
            np.testing.assert_array_equal(got[at], base[row])
