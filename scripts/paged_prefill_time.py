"""The clock behind the paged prefill kernel (ISSUE 49): one layer's
attention call of a chunk of T queries a row behind a cached prefix, at each
cell's heads x width x table, prefixes 0 / 2k / 8k / 14k (those its table
holds), a global layer and, where the cell has one, a window layer over its
ring:

* ``kernel``: ``ops/paged_prefill.py`` ``paged_prefill_attention``, own K / V
  read from the pool;
* ``xla``: what ``model_base._attn_block``'s ``gathered_mha`` does: the
  gather of the WHOLE table (a window layer: the ring's ``R`` pages) and
  ``attention.mha`` under the mask.

``--interplay`` answers another question, at SmallThinker's shape: whether a
decode step's ``paged_decode_attention`` call (32 rows of 3-5k tokens) runs
slower BEHIND the prefill kernel than alone or behind the gathered form (it
does not: 0.540 ms alone, 0.530 behind the kernel, 0.527 behind the gathered
form; my chip run, PR 49 - the traced slice of the faster closed loop reads a
higher ``step.decode_attn_ms`` because it holds other rows).

Prints one JSON line a case with ms a call, us a cached token (the slope from
prefix 0), the FLOP floor at the MXU's peak and the largest difference between
the two forms' results, and writes all of them to
``chiprun_out/paged_prefill_time.json``. A time comes from a chip only:
without a TPU it exits 2 (``utils/device.require_tpu``).

    python3 scripts/paged_prefill_time.py [--cells smallthinker,olmoe]
        [--rows 1] [--width 256] [--prefixes 0,2048,8192,14336] [--calls 10]
        [--reps 8] [--interplay]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12                     # v5e, bf16 (benchmark/harness/peaks.json)
BLOCK = 32

#: cell -> (query heads, kv heads, head lanes, table tokens, window): the
#: attention of each non-latent configuration under benchmark/configs, its
#: query heads as the pool's head slots carry them (olmo-hybrid: 30 in 32)
CELLS = {
    "smallthinker": (28, 4, 128, 15360, 4096),
    "olmoe": (16, 16, 128, 4096, 0),
    "granite": (32, 8, 64, 4096, 0),
    "olmo-hybrid": (32, 32, 128, 2048, 0),
    "qwen3-next": (16, 2, 256, 4096, 0),
}


def floor_us_a_token(hq: int, d: int, width: int) -> float:
    """Least microseconds a cached token a layer at the MXU's peak: a query
    row scores ``d`` lanes and sums ``d`` lanes of it."""
    return 4 * hq * width * d / PEAK_FLOPS * 1e6


def gathered_form(q, kp, vp, table, mask, scale: float):
    """What ``model_base._attn_block``'s ``gathered_mha`` does: the rows of
    the whole ``table``, their lanes split into heads, under ``mask``."""
    from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
    from neuronx_distributed_inference_tpu.ops import attention as attn_ops

    def gathered(pool):
        rows = bkv.gather_layer_kv(pool, 1, table)
        return rows.reshape(rows.shape[:2] + (-1, q.shape[-1]))
    return attn_ops.mha(q, gathered(kp), gathered(vp), mask, scale)


def _clock(fn, args, calls: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def interplay(calls: int, emit) -> None:
    """One decode call, one chunk call, and the two in turn, ``calls`` times
    inside one dispatch each: ms a turn, and the decode call's share of the
    turn behind either chunk form."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
    from neuronx_distributed_inference_tpu.ops import attention as attn_ops
    from neuronx_distributed_inference_tpu.ops import (decode_attention,
                                                       paged_prefill)
    hq, hkv, d, table_tokens, _ = CELLS["smallthinker"]
    rows, held, t, bf = 32, 160, 256, jnp.bfloat16
    mb = table_tokens // BLOCK
    rng = np.random.default_rng(0)
    slots, lanes = bkv.pool_page(hkv, d)
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    kp, vp = (jax.random.normal(
        k, (2, 1 + rows * held, BLOCK, slots, lanes), bf) for k in key[:2])
    table = np.zeros((rows, mb), np.int32)
    for r in range(rows):
        table[r, :held] = 1 + r * held + rng.permutation(held)
    table = jnp.asarray(table)
    lens = jnp.asarray(rng.integers(3000, held * BLOCK, size=rows), jnp.int32)
    q_step = jax.random.normal(key[2], (rows, hq, d), bf)
    new = jax.random.normal(key[3], (rows, hkv, d), bf)
    q_chunk = jax.random.normal(key[4], (1, t, hq, d), bf)
    first = jnp.asarray([held * BLOCK - 2 * t], jnp.int32)

    def step(q):
        return decode_attention.paged_decode_attention(
            q, kp, vp, new, new, 1, lens, table, scale=0.09)

    def kernel(q):
        return paged_prefill.paged_prefill_attention(
            q, kp, vp, 1, first, table[:1], scale=0.09)

    def gathered(q):
        return gathered_form(q, kp, vp, table[:1], attn_ops.decode_mask(
            first[:, None] + jnp.arange(t)[None], mb * BLOCK), 0.09)

    def turns(decode, chunk):
        def run(qs, qc):
            def body(_, outs):
                a, b = outs
                if chunk is not None:
                    b = chunk(qc + (b * 0).astype(bf))
                if decode is not None:
                    a = decode(qs + (a * 0).astype(bf))
                return a, b
            return jax.lax.fori_loop(
                0, calls, body, (jnp.zeros_like(qs), jnp.zeros_like(qc)))
        return jax.jit(run)

    ms = {name: _clock(turns(*forms), (q_step, q_chunk), 2) / calls
          for name, forms in {
              "decode": (step, None), "kernel": (None, kernel),
              "gathered": (None, gathered), "decode+kernel": (step, kernel),
              "decode+gathered": (step, gathered)}.items()}
    emit(interplay="smallthinker", **{k: round(v, 4) for k, v in ms.items()},
         decode_behind_kernel=round(ms["decode+kernel"] - ms["kernel"], 4),
         decode_behind_gathered=round(
             ms["decode+gathered"] - ms["gathered"], 4))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--prefixes", default="0,2048,8192,14336")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--forms", default="kernel,xla")
    ap.add_argument("--interplay", action="store_true")
    a = ap.parse_args(argv)
    from neuronx_distributed_inference_tpu.utils import device
    try:
        device.require_tpu()
    except device.NoAcceleratorError as e:
        print(f"paged_prefill_time: no TPU: {e}", file=sys.stderr)
        return 2
    import jax
    import jax.numpy as jnp
    import numpy as np
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
    from neuronx_distributed_inference_tpu.ops import attention as attn_ops
    from neuronx_distributed_inference_tpu.ops import paged_prefill

    t, b = a.width, a.rows
    forms = a.forms.split(",")
    bf = jnp.bfloat16
    scale = 0.09
    records = []

    def emit(**rec):
        records.append(rec)
        print(json.dumps(rec), flush=True)

    if a.interplay:
        interplay(100, emit)
    for cell in () if a.interplay else a.cells.split(","):
        hq, hkv, d, table_tokens, window = CELLS[cell]
        mb = table_tokens // BLOCK
        prefixes = [int(p) for p in a.prefixes.split(",")
                    if int(p) + t <= table_tokens]
        slots, lanes = bkv.pool_page(hkv, d)
        rng = np.random.default_rng(0)
        table = jnp.asarray(np.stack([
            1 + r * mb + rng.permutation(mb) for r in range(b)]), jnp.int32)
        key = jax.random.split(jax.random.PRNGKey(hq), 5)
        pool_k, pool_v = (jax.random.normal(
            k, (2, 1 + b * mb, BLOCK, slots, lanes), bf) for k in key[:2])
        q = jax.random.normal(key[2], (b, t, hq, d), bf)
        kinds = [("global", 0)] + ([("window", window)] if window else [])
        for kind, win in kinds:
            if win:
                ring = bkv.window_ring_pages(win, t, BLOCK)
                ring_k, ring_v = (jax.random.normal(
                    k, (2, b * ring, BLOCK, slots, lanes), bf)
                    for k in key[3:])
                spec = SimpleNamespace(sliding_window=win)
            base = {}

            def xla(q, kp, vp, tb, mask):
                return gathered_form(q, kp, vp, tb, mask, scale)

            def kernel(q, kp, vp, first, tb, w=win):
                return paged_prefill.paged_prefill_attention(
                    q, kp, vp, 1, first, tb, scale=scale, window=w)

            def chain(form):
                # ``--reps`` calls a dispatch, each behind the one before
                # it: a call of 0.05-0.5 ms hides under the host's ~0.2 ms
                # a dispatch otherwise
                def run(q, *rest):
                    return jax.lax.fori_loop(
                        0, a.reps, lambda _, out: form(
                            q + (out * 0).astype(q.dtype), *rest),
                        jnp.zeros(q.shape, q.dtype))
                return jax.jit(run)
            fns = {"xla": chain(xla), "kernel": chain(kernel)}
            for form in forms:
                fn = fns[form]
                for prefix in prefixes:
                    pos = prefix + jnp.arange(t, dtype=jnp.int32)[None] \
                        + jnp.zeros((b, 1), jnp.int32)
                    if win:
                        ri = model_base.window_ring_inputs(
                            spec, ring_k, b, pos, pos, table)
                        kp, vp = ring_k, ring_v
                        k_table, x_table, mask = (
                            ri["kernel_table"], ri["table"], ri["mask"])
                    else:
                        kp, vp, k_table, x_table = (pool_k, pool_v, table,
                                                    table)
                        mask = attn_ops.decode_mask(pos, mb * BLOCK)
                    args = ((q, kp, vp, pos[:, 0], k_table)
                            if form == "kernel"
                            else (q, kp, vp, x_table, mask))
                    try:
                        ms = _clock(fn, args, a.calls) / a.reps
                    except Exception as e:      # a shape a form refuses
                        emit(cell=cell, kind=kind, form=form, prefix=prefix,
                             failed=str(e)[:300])
                        continue
                    out = np.asarray(fn(*args), np.float32)
                    base.setdefault(form, (prefix, ms))
                    base.setdefault(("out", prefix), out)
                    p0, ms0 = base[form]
                    emit(cell=cell, kind=kind, rows=b, heads=hq, kv_heads=hkv,
                         lanes=d, width=t, table_tokens=table_tokens,
                         form=form, prefix=prefix, ms=round(ms, 4),
                         us_a_cached_token=(
                             round((ms - ms0) * 1e3 / (prefix - p0) / b, 4)
                             if prefix > p0 else None),
                         floor_us=round(floor_us_a_token(hq, d, t), 4),
                         max_diff=float(np.max(np.abs(
                             out - base[("out", prefix)]))))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_prefill_time.json", "w") as f:
        json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
