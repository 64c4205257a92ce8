"""The clock behind the selection kernel (ISSUE 51): one layer's selection of
a decode step (32 rows of one query) and of a one-row chunk (256 queries) at
``keye-vl2-videoqa-closed``'s shape (16 index heads of 64, ``topk`` 2048, a
table of 12,288 tokens, pages of 32 tokens = one bfloat16 tile), behind
prefixes 0 / 2048 / 6144 / 10240:

* ``kernel``: ``ops/index_select.py`` ``index_select``, the row's live index
  pages scored and searched in VMEM;
* ``xla``: what ``model_base._indexer_block`` does where the kernel is
  declined (``_gathered_select``): ``gather_index_rows`` of the WHOLE table,
  ``_index_scores``, ``topk_select``.

``--programs`` asks the question a rule of ``index_select.declined`` may rest
on: the cell's own step and chunk programs (``benchmark/configs/<config>``
built as the harness builds it, seeded weights, a pool of random index keys),
``paged.w1`` over 32 rows and ``paged.w256`` over one, each traced twice -
with the kernel, and with ``declined`` made to answer for this script alone -
at the same prefixes: ms a dispatch, device time by the host's clock around
``block_until_ready``.

Prints one JSON line a case and writes all of them to
``chiprun_out/index_select_time.json``; ``same`` is the share of the table's
positions on which the two forms' selections agree (bfloat16 products summed
in another order move a score's last bits, and a key at the threshold with
it). A time comes from a chip only: without a TPU it exits 2
(``utils/device.require_tpu``).

    python3 scripts/index_select_time.py [--widths 1,256]
        [--prefixes 0,2048,6144,10240] [--calls 10] [--reps 8]
        [--programs] [--config keye-vl-2.0-30b-a3b]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BLOCK = 32
HEADS, DIM, TOPK, TABLE_TOKENS, LAYERS = 16, 64, 2048, 12288, 2
#: width -> rows of the program that runs it (the step: the batch; a chunk:
#: one row)
ROWS = {1: 32, 64: 1, 256: 1}


def live_bytes(rows: int, prefix: int, width: int) -> int:
    """Bytes of index keys the rows hold (what a selection has to read)."""
    return rows * (prefix + width) * DIM * 2


def _clock(fn, args, calls: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def gathered_form(qi, w, pool, layer, positions, table):
    """What ``model_base._indexer_block`` does where the kernel is declined."""
    from neuronx_distributed_inference_tpu.models import model_base
    return model_base._gathered_select(
        SimpleNamespace(index_heads=HEADS, index_dim=DIM, topk=TOPK), qi, w,
        pool, layer, positions, table)


def alone(a, emit) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
    from neuronx_distributed_inference_tpu.ops import index_select
    bf = jnp.bfloat16
    mb = TABLE_TOKENS // BLOCK
    prow, lanes = bkv.index_page(DIM, BLOCK)
    for t in (int(x) for x in a.widths.split(",")):
        b = ROWS[t]
        rng = np.random.default_rng(0)
        table = jnp.asarray(np.stack([
            1 + r * mb + rng.permutation(mb) for r in range(b)]), jnp.int32)
        key = jax.random.split(jax.random.PRNGKey(t), 3)
        pool = jax.random.normal(key[0], (LAYERS, 1 + b * mb, prow, lanes), bf)
        qi = jax.random.normal(key[1], (b, t, HEADS, DIM), bf)
        w = jax.random.normal(key[2], (b, t, HEADS), bf)

        def kernel(qi, w, pos):
            return index_select.index_select(qi, w, pool, 1, pos, table,
                                             topk=TOPK)

        def xla(qi, w, pos):
            return gathered_form(qi, w, pool, 1, pos, table)

        def chain(form):
            # ``--reps`` calls a dispatch, each behind the one before it
            # (the weights take the count selected, scaled to nothing: a
            # dependence no simplifier removes, so no call is hoisted)
            def run(qi, w, pos):
                def body(_, sel):
                    n = jnp.sum(sel, axis=-1, dtype=jnp.float32)
                    return form(qi, w + (n * 1e-30)[..., None].astype(bf),
                                pos)
                return jax.lax.fori_loop(
                    0, a.reps, body,
                    jnp.zeros((b, t, TABLE_TOKENS), jnp.bool_))
            return jax.jit(run)
        fns = {"kernel": chain(kernel), "xla": chain(xla)}
        for prefix in (int(p) for p in a.prefixes.split(",")):
            if prefix + t > TABLE_TOKENS:
                continue
            pos = prefix + jnp.arange(t, dtype=jnp.int32)[None] \
                + jnp.zeros((b, 1), jnp.int32)
            got = {}
            for form, fn in fns.items():
                ms = _clock(fn, (qi, w, pos), a.calls) / a.reps
                got[form] = np.asarray(fn(qi, w, pos))
                emit(where="alone", form=form, rows=b, width=t, prefix=prefix,
                     ms=round(ms, 4),
                     live_gb_s=round(live_bytes(b, prefix, t) / ms / 1e6, 1),
                     selected=int(got[form].sum()),
                     same=float((got[form] == got["kernel"]).mean()))


def programs(a, emit) -> None:
    """The cell's own ``paged.w1`` and ``paged.w<width>`` with either form of
    the selection inside."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build
    from neuronx_distributed_inference_tpu.ops import index_select
    cfg = build.load_json("configs", a.config + ".json")
    serve = cfg["serve"]
    bs, mb = serve["pa_block_size"], serve["seq_len"] // serve["pa_block_size"]
    app = build.build_app(cfg)
    app.init_random_weights(seed=a.seed).init_cache()
    pool = app.cache["k_idx"]
    app.cache["k_idx"] = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), pool.shape, pool.dtype),
        pool.sharding)
    del pool
    rng = np.random.default_rng(0)
    batch = serve["batch_size"]
    table = np.stack([1 + r * mb + rng.permutation(mb) for r in range(batch)]
                     ).astype(np.int32)
    really = index_select.declined
    for form in ("kernel", "xla"):
        index_select.declined = really if form == "kernel" else (
            lambda *args: really(*args) or "timed without")
        app._compiled.clear()
        for t in (int(x) for x in a.widths.split(",")):
            b = batch if t == 1 else 1
            for prefix in (int(p) for p in a.prefixes.split(",")):
                if prefix + t > serve["seq_len"]:
                    continue
                pos = (prefix + np.arange(t, dtype=np.int32)[None]
                       + np.zeros((b, 1), np.int32))
                slots = (np.take_along_axis(table[:b], pos // bs, axis=1) * bs
                         + pos % bs).astype(np.int32)
                ids = rng.integers(1, 1000, size=(b, t)).astype(np.int32)
                last = np.full((b,), t - 1, np.int32)

                def run():
                    return app._run_paged(ids, pos, slots, table[:b],
                                          last)["tokens"]
                ms = _clock(lambda: run(), (), a.calls)
                emit(where="program", form=form, rows=b, width=t,
                     prefix=prefix, ms=round(ms, 4),
                     notes=sorted(r for s, _, r in app.paged_program_notes(
                         b, t) if s == "index_select"))
    index_select.declined = really


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default="1,256")
    ap.add_argument("--prefixes", default="0,2048,6144,10240")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--programs", action="store_true")
    ap.add_argument("--config", default="keye-vl-2.0-30b-a3b")
    ap.add_argument("--seed", type=int, default=2147483751)
    a = ap.parse_args(argv)
    from neuronx_distributed_inference_tpu.utils import device
    try:
        device.require_tpu()
    except device.NoAcceleratorError as e:
        print(f"index_select_time: no TPU: {e}", file=sys.stderr)
        return 2
    records = []

    def emit(**rec):
        records.append(rec)
        print(json.dumps(rec), flush=True)

    (programs if a.programs else alone)(a, emit)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "index_select_time" + ("-programs" if a.programs else "")
    with open(f"chiprun_out/{name}.json", "w") as f:
        json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
