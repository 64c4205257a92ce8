"""The clock behind the latent prefill kernel's form and its decline rule
(ISSUE 48): one row's chunk of T queries behind a cached prefix, one layer's
call, at 64 heads (LongCat-Flash) and 128 (DeepSeek-V3), prefixes 0 / 2k / 7k:

* ``kernel``: ``ops/mla_prefill.py`` ``mla_prefill_attention`` (the query's
  fold and ``W_UV`` around it included), own rows read from the pool;
* ``xla expanded`` / ``xla absorbed``: ``model_base._mla_attend``, the two
  forks of the XLA form; its parts are read off the prefixes (prefix 0 is the
  own-token part and the merge, the slope the walk);
* ``projections``: the five MLA matrices of a layer at the width of
  ``--hidden``, as plain matmuls on T rows.

Prints one JSON line a case with ms a call and us a cached token (the slope
from prefix 0) beside the FLOP floors of both forms, and writes all of them to
``chiprun_out/mla_prefill_time.json``. A time comes from a chip only: without
a TPU it exits 2 (``utils/device.require_tpu``).

    python3 scripts/mla_prefill_time.py [--heads 64,128] [--width 256]
        [--prefixes 0,2048,7168] [--tile-rows 1024] [--calls 20]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RANK, ROPE, NOPE, V, LANES, BLOCK = 512, 64, 128, 128, 640, 32
PEAK_FLOPS = 197e12                     # v5e, bf16 (benchmark/harness/peaks.json)


def floor_us_a_token(heads: int, width: int, form: str) -> float:
    """Least microseconds a cached token a layer at the MXU's peak: the
    absorbed form scores ``rank + rope`` lanes and sums ``rank`` a query
    head; the expanded form pays ``kv_b_proj`` once a token and scores
    ``nope + rope``, sums ``v``."""
    if form == "absorbed":
        flop = 2 * width * heads * (RANK + ROPE + RANK)
    else:
        flop = 2 * (RANK * heads * (NOPE + V)
                    + width * heads * (NOPE + ROPE + V))
    return flop / PEAK_FLOPS * 1e6


def _clock(fn, args, calls: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="128,64")
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--prefixes", default="0,2048,7168")
    ap.add_argument("--tile-rows", default="")
    ap.add_argument("--hidden", type=int, default=7168)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--forms", default="kernel,expanded,absorbed,projections")
    a = ap.parse_args(argv)
    from neuronx_distributed_inference_tpu.utils import device
    try:
        device.require_tpu()
    except device.NoAcceleratorError as e:
        print(f"mla_prefill_time: no TPU: {e}", file=sys.stderr)
        return 2
    import jax
    import jax.numpy as jnp
    import numpy as np
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.ops import mla_prefill

    t = a.width
    prefixes = [int(p) for p in a.prefixes.split(",")]
    tiles = [int(x) for x in a.tile_rows.split(",") if x] or [
        mla_prefill.MLA_PREFILL_TILE_ROWS]
    forms = a.forms.split(",")
    mb = -(-(max(prefixes) + t) // BLOCK)
    rng = np.random.default_rng(0)
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (5, 1 + mb, BLOCK, 1, LANES), jnp.bfloat16)
    pool = pool.at[..., RANK + ROPE:].set(0)
    table = jnp.asarray(1 + rng.permutation(mb)[None], jnp.int32)
    bf = jnp.bfloat16
    records = []

    def emit(**rec):
        records.append(rec)
        print(json.dumps(rec), flush=True)

    for heads in (int(h) for h in a.heads.split(",")):
        spec = SimpleNamespace(
            mla=SimpleNamespace(kv_lora_rank=RANK, qk_rope_head_dim=ROPE,
                                qk_nope_head_dim=NOPE, v_head_dim=V,
                                latent_dim=RANK + ROPE),
            scale=0.1, kv_scale=None)
        keys = jax.random.split(jax.random.PRNGKey(heads), 4)
        q_nope = jax.random.normal(keys[0], (1, t, heads, NOPE), bf)
        q_rot = jax.random.normal(keys[1], (1, t, heads, ROPE), bf)
        lat = jax.random.normal(keys[2], (1, t, RANK + ROPE), bf)
        w_kvb = jax.random.normal(keys[3], (RANK, heads, NOPE + V), bf) * .05
        base = {}
        for form in forms:
            if form == "projections":
                continue
            for tile in (tiles if form == "kernel" else [0]):
                for prefix in prefixes:
                    first = jnp.asarray([prefix], jnp.int32)
                    if form == "kernel":
                        mla_prefill.MLA_PREFILL_TILE_ROWS = tile
                        jax.clear_caches()
                        fn = jax.jit(lambda qn, qr, w, p, f, tb:
                                     mla_prefill.mla_prefill_attention(
                                         qn, qr, w, p, 2, f, tb,
                                         scale=spec.scale, rank=RANK))
                        args = (q_nope, q_rot, w_kvb, pool, first, table)
                    else:
                        fn = jax.jit(lambda qn, qr, ln, w, p, f, tb, ab=(
                            form == "absorbed"): model_base._mla_attend(
                                spec, qn, qr, ln, w, p, 2, tb,
                                f[:, None] + jnp.arange(t)[None], ab))
                        args = (q_nope, q_rot, lat, w_kvb, pool, first,
                                table)
                    try:
                        ms = _clock(fn, args, a.calls)
                    except Exception as e:     # a tile Mosaic refuses
                        emit(heads=heads, form=form, tile=tile,
                             prefix=prefix, failed=str(e)[:300])
                        continue
                    key = (form, tile)
                    base.setdefault(key, (prefix, ms))
                    p0, ms0 = base[key]
                    emit(heads=heads, width=t, form=form, tile_rows=tile,
                         prefix=prefix, ms=round(ms, 4),
                         us_a_cached_token=(
                             round((ms - ms0) * 1e3 / (prefix - p0), 4)
                             if prefix > p0 else None),
                         floor_absorbed=round(
                             floor_us_a_token(heads, t, "absorbed"), 4),
                         floor_expanded=round(
                             floor_us_a_token(heads, t, "expanded"), 4))
        if "projections" in forms:
            h = a.hidden
            x = jax.random.normal(keys[0], (t, h), bf)
            ws = [jax.random.normal(keys[1], s, bf) * .02 for s in (
                (h, 1536), (1536, heads * (NOPE + ROPE)), (h, RANK + ROPE),
                (heads * V, h))]

            def proj(x, qa, qb, kva, o):
                q = jnp.dot(jnp.dot(x, qa), qb)
                return (q, jnp.dot(x, kva),
                        jnp.dot(q[:, :heads * V], o))
            emit(heads=heads, width=t, form="projections", hidden=h,
                 ms=round(_clock(jax.jit(proj), (x, *ws), a.calls), 4))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_prefill_time.json", "w") as f:
        json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
