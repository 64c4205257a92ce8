"""The clock behind the paged decode kernel's two inner loops (ISSUE 57): the
Pallas call of ``ops/decode_attention.py`` ALONE (``paged_decode_attention``:
no projections, no cache write) at 32 rows of 2k / 6k / 10k cached tokens,
pages of 32 tokens, bf16, window 0 and 4096, for

* Command A+'s geometry (128 query heads over 8 kv heads of 128 lanes) under
  the block-diagonal form it had (``blockdiag``: ONE page a block) and the
  kv-row form (``plan``; ``rows-<pages>`` with another count of pages a
  block). The forms that LOST on this clock (a kv row scored, soft-maxed and
  summed before the next; a float32 copy of the block in place of the 32-bit
  view of row pairs; a loop over kv rows in place of unrolled copies) are in
  ``paged_block_plan``'s docstring with their numbers, not in the code;
* OLMoE's (16 x 1), granite's (8 heads of 64 under 4 query heads: 4 rows x 8
  after the fold) and olmo-hybrid's (32 x 1) under the form they keep;
* (ISSUE 66, the walk's rows as ONE stream of copies) the geometries whose
  rows are a block or two, at the lengths and windows their cells run
  (:data:`ALONE`, where the flags name none): SmallThinker's and Keye's (4 kv
  heads of 128 lanes folded to ONE row of 512 under 28 / 32 query rows, window
  0 and a ring of 4096; Keye's with its learned selection of 2048 tokens a
  row), phi4-flash's (ten 128-lane pairs folded to one row of 1280 under 40
  query rows: the shared pool at ~5k tokens a row and a ring of 512) and
  nemotron's short calls (2 kv heads folded to one row, ~0.5k / ~1k tokens).
  Parent against change: run this file from a copy of each tree (``git
  archive`` of the parent, this script laid over it), not a switch here.

Calls run back to back inside ONE program, each depending on the one before
through the lengths (one host dispatch a call costs more than the kernel, and
XLA shares one result between identical calls: ROADMAP trap 13). Prints one
JSON line a case with ms a call and the share of ``paged_decode_min_bytes``
(``benchmark/harness/kernel_bytes.py``: the live tokens' K and V inside the
window, the queries and outputs) at the chip's 819 GB/s, then the table, and
writes all of it to ``chiprun_out/<--out>``. A time comes from
a chip only: without a TPU it exits 2 (``utils/device.require_tpu``). Run it
under ``timeout``.

    python3 scripts/paged_decode_time.py [--cells command-a-plus,olmoe,..]
        [--forms plan,blockdiag,rows-16] [--tokens 2048,..]
        [--windows 0,4096] [--calls 12] [--out paged_decode_time.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK, ROWS, PEAK_BYTES = 32, 32, 819e9
#: cell -> (query heads, kv heads, head_dim): tests/test_decode_attention.py
CELLS = {"command-a-plus": (128, 8, 128), "olmoe": (16, 16, 128),
         "granite": (32, 8, 64), "olmo-hybrid": (32, 32, 128),
         "smallthinker": (28, 4, 128), "keye": (32, 4, 128),
         "phi4-flash": (40, 10, 128), "nemotron": (32, 2, 128)}
#: cell -> (tokens a row, windows) where ``--tokens`` / ``--windows`` name
#: none: what its benchmark cell's rows hold and its layer kinds' windows
ALONE = {"smallthinker": ((2048, 6144), (0, 4096)),
         "keye": ((2048, 6144), (0, 4096)),
         "phi4-flash": ((5120,), (0, 512)), "nemotron": ((512, 1024), (0,))}
TOKENS, WINDOWS = (2048, 6144, 10240), (0, 4096)
#: cell -> tokens a row's query attends (a learned sparse selection: one more
#: input of the call, the walk is the live pages' all the same)
SELECTS = {"keye": 2048}


def min_bytes(cell: str, tokens: int, window: int, rows: int = ROWS) -> float:
    """``paged_decode_min_bytes`` of one call: ``rows`` rows of ``tokens``
    cached tokens, those inside the window where there is one."""
    hq, hkv, d = CELLS[cell]
    seen = min(tokens, window) if window else tokens
    return rows * (seen * 2 * hkv * d * 2 + 2 * hq * d * 2)


def row_lengths(tokens: int, rows: int = ROWS):
    """Mixed lengths about ``tokens``: an even spread of +- a quarter, so
    that rows end inside different pages of their last block."""
    import numpy as np
    return (tokens * np.linspace(0.75, 1.25, rows)).astype(np.int64)


def selection(rng, lens, topk: int, width: int):
    """(rows, width) bool: ``topk`` of each row's cached positions, drawn
    evenly, and the row's own."""
    import numpy as np
    select = np.zeros((len(lens), width), bool)
    for i, n in enumerate(lens):
        select[i, rng.choice(int(n), min(topk, int(n)), replace=False)] = True
        select[i, int(n)] = True
    return select


def _set_form(da, form: str, was) -> None:
    """Point the kernel's module at ``form``: ``plan`` (what it gives),
    ``blockdiag`` (the block-diagonal plan whatever the geometry) or
    ``rows-<pages>`` (the kv-row form at that many pages a block); ``was``
    is the module's own ``(paged_block_plan, PAGED_SCORE_TILE_ELEMENTS)``."""
    da.paged_block_plan, da.PAGED_SCORE_TILE_ELEMENTS = was
    if form == "blockdiag":
        da.paged_block_plan = da._blockdiag_plan
    elif form != "plan":
        da.PAGED_SCORE_TILE_ELEMENTS = was[1] * int(form.split("-")[1]) // 8


def _clock(fn, args, calls: int, reps: int = 4) -> float:
    """ms a call on the device: ``calls`` calls inside one program, chained
    through the lengths."""
    import jax
    import jax.numpy as jnp
    q, kp, vp, nk, nv, lens, table = args

    @jax.jit
    def many(q, kp, vp, nk, nv, lens, table):
        def body(_, tot):
            bump = (tot > 3e38).astype(lens.dtype)
            out = fn(q, kp, vp, nk, nv, lens=lens + bump, block_table=table)
            return tot + out[0, 0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, calls, body, jnp.float32(0))
    jax.block_until_ready(many(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(many(*args))
        best = min(best, (time.perf_counter() - t0) / calls * 1e3)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--forms", default="blockdiag,plan")
    ap.add_argument("--tokens", default="")
    ap.add_argument("--windows", default="")
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--out", default="paged_decode_time.json")
    a = ap.parse_args(argv)
    from neuronx_distributed_inference_tpu.utils import device
    try:
        device.require_tpu()
    except device.NoAcceleratorError as e:
        print(f"paged_decode_time: no TPU: {e}", file=sys.stderr)
        return 2
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from neuronx_distributed_inference_tpu.ops import decode_attention as da

    was = (da.paged_block_plan, da.PAGED_SCORE_TILE_ELEMENTS)
    bf = jnp.bfloat16
    records = []
    for cell in a.cells.split(","):
        hq, hkv, d = CELLS[cell]
        tokens, windows = ALONE.get(cell, (TOKENS, WINDOWS))
        tokens = [int(t) for t in a.tokens.split(",")] if a.tokens else tokens
        windows = ([int(w) for w in a.windows.split(",")] if a.windows
                   else windows)
        mb = -(-(int(max(tokens) * 1.25) + 1) // BLOCK)
        fold = da.paged_pool_fold(hkv, d)
        rng = np.random.default_rng(hq)
        table = jnp.asarray(
            (1 + rng.permutation(ROWS * mb)).reshape(ROWS, mb), jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(hq), 5)
        # a layer of pool as the application stores a page (pool_page)
        kp, vp = (jax.random.normal(
            k, (1, 1 + ROWS * mb, BLOCK, hkv // fold, d * fold), bf)
            for k in keys[:2])
        q = jax.random.normal(keys[2], (ROWS, hq, d), bf)
        nk, nv = (jax.random.normal(k, (ROWS, hkv, d), bf) for k in keys[3:])
        forms = a.forms.split(",") if cell == "command-a-plus" else ["plan"]
        for form in forms:
            for window in windows:
                for n in tokens:
                    _set_form(da, form, was)
                    jax.clear_caches()
                    plan = da.paged_block_plan(BLOCK, hkv, hq // hkv, d, bf, mb)
                    fn = functools.partial(
                        da.paged_decode_attention, layer=jnp.int32(0),
                        scale=d ** -0.5, window=jnp.int32(window))
                    rec = dict(cell=cell, form=form, note=plan.note(fold > 1),
                               tokens=n, window=window)
                    lens = jnp.asarray(row_lengths(n), jnp.int32)
                    if cell in SELECTS and not window:
                        fn = functools.partial(fn, select=jnp.asarray(
                            selection(rng, row_lengths(n), SELECTS[cell],
                                      mb * BLOCK)))
                        rec["select"] = SELECTS[cell]
                    try:
                        ms = _clock(fn, (q, kp, vp, nk, nv, lens, table),
                                    a.calls)
                        need = sum(min_bytes(cell, int(x), window, 1)
                                   for x in row_lengths(n))
                        rec.update(ms=round(ms, 4), share=round(
                            100 * need / PEAK_BYTES / (ms * 1e-3), 1))
                    except Exception as e:      # a form Mosaic refuses
                        rec["failed"] = str(e)[-300:]
                    records.append(rec)
                    print(json.dumps(rec), flush=True)
    _set_form(da, "plan", was)
    print(f"{'cell':>15} {'form':>16} {'window':>6}  "
          "tokens a row: ms a call (% of bytes)")
    seen = []
    for r in records:
        key = (r["cell"], r["form"], r["window"])
        if key not in seen:
            seen.append(key)
            print(f"{key[0]:>15} {key[1]:>16} {key[2]:>6}  " + "  ".join(
                f"{x['tokens']:>5}: {x.get('ms', float('nan')):>6.3f} "
                f"({x.get('share', 0):>4.1f})" for x in records
                if (x["cell"], x["form"], x["window"]) == key))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", a.out), "w") as f:
        json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
