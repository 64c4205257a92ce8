"""The clock behind the latent decode kernel's block loop (ISSUE 53): the
Pallas call of ``ops/mla_decode.py`` ALONE (``latent_rows_attention``: no
fold, no ``W_UV``) at the two latent cells' shapes - 64 heads over 28 live
rows of 32 at ~3.6k tokens (LongCat-Flash), 128 heads over 32 rows at ~3.85k
(DeepSeek-V3), lengths mixed from 768 up, pages of 32 tokens, bf16 - stopped
short at three places:

* ``copies``: the walk with its copies and waits, no dot: against the stored
  row's bytes at the chip's bandwidth it reads what the copies cost;
* ``scores``: copies + the score dot and its running maximum;
* ``whole``: the kernel.

``scores - copies`` and ``whole - scores`` are what the two dots add ON TOP of
the copies (what of them the copies do not hide). Calls run back to back
inside ONE program, each depending on the one before: a host dispatch a call
costs more than the kernel. Prints one JSON line a case with ms a call and ns
a cached token beside the floors (bytes: the stored row at 819 GB/s; FLOP: the
absorbed form at the MXU's peak), then the table, and writes all of it to
``chiprun_out/mla_decode_time.json``. A time comes from a chip only: without a
TPU it exits 2 (``utils/device.require_tpu``).

    python3 scripts/mla_decode_time.py [--heads 128,64] [--parts
        copies,scores,whole] [--block-tokens 512,1024] [--slots 3] [--calls 20]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RANK, ROPE, LANES, BLOCK = 512, 64, 640, 32
PEAK_FLOPS = 197e12             # v5e, bf16 (benchmark/harness/peaks.json)
PEAK_BYTES = 819e9
#: heads -> (rows, dead rows, mean tokens a live row, context): the cells'
SHAPES = {128: (32, (), 3850, 12288), 64: (32, (5, 13, 14, 30), 3600, 8192)}


def floors_ns_a_token(heads: int) -> dict:
    """Least nanoseconds a cached token a call: its stored row's bytes at the
    chip's bandwidth, and the absorbed form's multiplications (``rank + rope``
    lanes scored, ``rank`` summed, a head) at the MXU's peak."""
    return {"bytes": LANES * 2 / PEAK_BYTES * 1e9,
            "flop": 2 * heads * (RANK + ROPE + RANK) / PEAK_FLOPS * 1e9}


def row_lengths(heads: int, seed: int = 0):
    """The cell's mixed lengths: lognormal about the mean, 768 and up, under
    the context, dead rows 0."""
    import numpy as np
    rows, dead, mean, ctx = SHAPES[heads]
    rng = np.random.default_rng(seed)
    lens = np.clip(np.exp(rng.normal(np.log(mean) - 0.18, 0.6, size=rows)),
                   768, ctx - 1)
    lens = np.clip(lens * (mean * rows / lens.sum()), 1, ctx - 1)
    lens = lens.astype(np.int64)
    lens[list(dead)] = 0
    return lens


def _clock(fn, args, calls: int, reps: int = 5) -> float:
    """ms a call on the device: ``calls`` calls inside one program, chained
    through the lengths."""
    import jax
    import jax.numpy as jnp
    q, new, pool, layer, lens, table = args

    @jax.jit
    def many(q, new, pool, lens, table):
        def body(_, tot):
            bump = (tot > 3e38).astype(lens.dtype)
            out = fn(q, new, pool, layer, lens + bump, table)
            return tot + out[0, 0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, calls, body, jnp.float32(0))
    a = (q, new, pool, lens, table)
    jax.block_until_ready(many(*a))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(many(*a))
        best = min(best, (time.perf_counter() - t0) / calls * 1e3)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="128,64")
    ap.add_argument("--parts", default="copies,scores,whole")
    ap.add_argument("--block-tokens", default="")
    ap.add_argument("--slots", default="")
    ap.add_argument("--calls", type=int, default=20)
    a = ap.parse_args(argv)
    from neuronx_distributed_inference_tpu.utils import device
    try:
        device.require_tpu()
    except device.NoAcceleratorError as e:
        print(f"mla_decode_time: no TPU: {e}", file=sys.stderr)
        return 2
    import jax
    import jax.numpy as jnp
    import numpy as np
    from neuronx_distributed_inference_tpu.ops import mla_decode

    kernel, tile = mla_decode._kernel, mla_decode.MLA_SCORE_TILE_ELEMENTS
    blocks = [int(x) for x in a.block_tokens.split(",") if x] or [0]
    slots = [int(x) for x in a.slots.split(",") if x] or [
        mla_decode.MLA_SLOTS]
    bf = jnp.bfloat16
    records = []
    for heads in (int(h) for h in a.heads.split(",")):
        rows, _, _, ctx = SHAPES[heads]
        lens = row_lengths(heads)
        live = int(lens.sum())
        mb = ctx // BLOCK
        rng = np.random.default_rng(heads)
        table = jnp.asarray(
            (1 + rng.permutation(rows * mb)).reshape(rows, mb), jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(heads), 3)
        pool = jax.random.normal(keys[0], (2, 1 + rows * mb, BLOCK, 1, LANES),
                                 bf).at[..., RANK + ROPE:].set(0)
        q = jax.random.normal(keys[1], (rows, heads, LANES), bf
                              ).at[..., RANK + ROPE:].set(0)
        new = jax.random.normal(keys[2], (rows, 1, LANES), bf
                                ).at[..., RANK + ROPE:].set(0)
        args = (q, new, pool, 1, jnp.asarray(lens, jnp.int32), table)
        for tokens in blocks:
            for n_slots in slots:
                for part in a.parts.split(","):
                    # 0: the block the kernel picks for the heads
                    mla_decode.MLA_SCORE_TILE_ELEMENTS = tokens * heads or tile
                    mla_decode.MLA_SLOTS = n_slots
                    mla_decode._kernel = functools.partial(kernel, parts=part)
                    jax.clear_caches()
                    fn = functools.partial(mla_decode.latent_rows_attention,
                                           scale=0.1, rank=RANK)
                    rec = dict(heads=heads, rows=rows, live_rows=int(
                        (lens > 0).sum()), live_tokens=live,
                        block_tokens=BLOCK * mla_decode.block_pages(
                            BLOCK, LANES, bf, mb, heads), slots=n_slots,
                        part=part,
                        tiles="heads-held" if mla_decode.heads_held(heads)
                        else "tokens-held")
                    try:
                        ms = _clock(fn, args, a.calls)
                        rec.update(ms=round(ms, 4), ns_a_token=round(
                            ms * 1e6 / live, 3), **{
                                f"floor_{k}_ns": round(v, 3) for k, v in
                                floors_ns_a_token(heads).items()})
                    except Exception as e:     # a block Mosaic refuses
                        rec["failed"] = str(e)[-300:]
                    records.append(rec)
                    print(json.dumps(rec), flush=True)
    print(f"{'heads':>5} {'block':>5} {'slots':>5} " + " ".join(
        f"{p:>8}" for p in a.parts.split(",")) + "   ms a call")
    seen = []
    for r in records:
        key = (r["heads"], r["block_tokens"], r["slots"])
        if key not in seen:
            seen.append(key)
            print(f"{key[0]:>5} {key[1]:>5} {key[2]:>5} " + " ".join(
                f"{x.get('ms', float('nan')):>8.4f}" for x in records
                if (x["heads"], x["block_tokens"], x["slots"]) == key))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_decode_time.json", "w") as f:
        json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
