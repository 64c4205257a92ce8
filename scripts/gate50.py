"""PR 50's own check of a ``KeyeVL2`` configuration (ISSUE 50, point 4), on
whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``), then
the chip. Loaded by ``tests/test_keye_vl2_paged.py`` at a toy size, so it
stays runnable (ROADMAP C13).

1. ``scripts/gate40.py``'s :func:`gate_and_controls`, which reads nothing of
   one architecture: the configuration's logit gate on the twin at the file's
   widths (``gate.config``: two layers, ``topk`` shrunk to 32 FOR THE TWIN so
   that 128 tokens a row cross it), and every control that must fail it - the
   reference with one deliberate fault (``references/KeyeVL2.py``
   ``CONTROLS``) or on fp8-rounded weights - against the SAME served logits.
2. :func:`long_walk`: what the harness's gate of 128 tokens a row cannot
   see, the PUBLISHED ``topk`` at the timed lengths. ``rows`` prompts of at
   least 8192 tokens walked through ``PagedEngineAdapter`` with the
   configuration's own keywords (chunks of 256 behind a growing prefix: the
   timed chunk program, its indexer over thousands of cached index keys, the
   prefill kernel with the selection), then decode steps through the three
   pools (the timed decode program; a row that ends its prompt early decodes
   on beside the other rows' chunks), every position's logits against the
   reference's on the tokens the row was fed, a block of queries at a time
   under ``jax.default_matmul_precision("highest")``. Past position ``topk``
   a query attends ``topk`` of its tokens and the controls that only a long
   row can fail (:data:`LONG_CONTROLS`) are judged there, against the same
   served logits.

    python3 scripts/gate50.py [--config keye-vl-2.0-30b-a3b] [--seed n]
        [--long 8192] [--rows 4] [--controls a,b] [--skip-gate]

writes ``chiprun_out/gate50-<backend>.json``. No timing is taken or
printed.
"""

import argparse
import functools
import gc
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: faults of the selection that a row past ``topk`` tokens shows
LONG_CONTROLS = ("dense_attention", "half_topk", "keys_not_rotated")


@functools.lru_cache(maxsize=None)
def _gate40():
    spec = importlib.util.spec_from_file_location(
        "gate40", os.path.join(ROOT, "scripts", "gate40.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def gate_and_controls(cfg, seed, controls=None, served_precision=None):
    """:func:`gate40.gate_and_controls` of ``cfg``."""
    return _gate40().gate_and_controls(cfg, seed, controls, served_precision)


def long_walk(cfg, seed, tokens, rows=4, new_tokens=16, block=512,
              served_precision=None, controls=LONG_CONTROLS, twin=None):
    """``rows`` prompts of ``tokens`` tokens walked through
    ``PagedEngineAdapter(**cfg["adapter"])`` in chunks, then ``new_tokens``
    decode steps until every row has decoded at least ``new_tokens``
    positions, on the twin ``twin`` (default: the gate's depth at the
    PUBLISHED ``sa_config``); every served position's logits against the
    reference's on the tokens the served path was fed (``block`` queries a
    pass), by the gate's tolerance, and against the reference under each of
    ``controls``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build, weights
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    g40 = _gate40()
    gate, n = cfg["gate"], tokens
    if twin is None:
        twin = {k: v for k, v in build.gate_overrides(gate).items()
                if k != "sa_config"}
    hf = build.hf_config(cfg, twin)
    ref = build.load_reference(hf["model_type"])
    table = ref.weight_shapes(hf)
    topk = hf["sa_config"]["topk"]
    rng = np.random.default_rng([seed, 0x6c6f6e67])
    prompts = rng.integers(1, hf["vocab_size"], size=(rows, n),
                           dtype=np.int64).astype(np.int32)
    w = weights.make_weights(table, seed)
    bs = cfg["serve"]["pa_block_size"]
    width = max(cfg["serve"]["context_encoding_buckets"])
    # a row that ends its prompt early decodes on beside the other rows'
    # chunks (one chunk before each step under the adapter's budget)
    reach = n + new_tokens + rows * (-(-n // width) + 1)
    blocks = -(-(reach + 2 * bs) // bs)
    app = build.build_app(cfg, overrides=twin, output_logits=True,
                          serve=dict(cfg["serve"], batch_size=rows,
                                     seq_len=blocks * bs,
                                     pa_num_blocks=rows * blocks + 8))
    app._put_params(app.family.convert_hf_state_dict(
        g40._view(table, w, cfg), app.spec))
    app.init_cache()
    vocab = hf["vocab_size"]
    got = np.zeros((rows, reach, vocab), np.float32)
    fed = np.ones((rows, reach), np.int32)
    seen = np.zeros((rows, reach), bool)
    shapes = set()
    inner = app._run_paged

    def tap(ids_, pos, slots, bt, last, *a, **kw):
        # every logit the served path computes and the token it was
        # computed for, filed under the row whose block table it carries
        o = inner(ids_, pos, slots, bt, last, *a, **kw)
        shapes.add(tuple(np.shape(ids_)))
        owner = {blk[0]: sid for sid, blk in app.kv_mgr.tables.items()}
        logits, ids_ = np.asarray(o["logits"]), np.asarray(ids_)
        pos, slots, bt = np.asarray(pos), np.asarray(slots), np.asarray(bt)
        for r in range(logits.shape[0]):
            live = np.nonzero(slots[r] >= 0)[0]
            if live.size:
                row, at = owner[int(bt[r, 0])], pos[r, live]
                got[row, at] = logits[r][live, :vocab]
                fed[row, at] = ids_[r, live]
                seen[row, at] = True
        return o
    app._run_paged = tap
    with g40._precision(served_precision):
        ad = PagedEngineAdapter(app, **cfg.get("adapter", {}))
        sids = list(range(rows))
        ad.add_requests(sids, [prompts[r].tolist() for r in sids])
        # deferred prefill, a chunk before each step; then until every row
        # has decoded new_tokens positions through the pools
        while not seen[:, n + new_tokens - 1].all():
            ad.step()
    out = {"tokens": n, "rows": rows, "topk": topk,
           "positions_served": int(seen.sum()),
           "program_shapes": sorted(shapes),
           "host_stats": {k: v for k, v in ad.host_stats.items()
                          if k.startswith(("sparse", "kv_index",
                                           "prefill_dispatches",
                                           "dispatches"))},
           "notes": sorted({(x["site"], x["path"], x["reason"])
                            for x in app.warmup_state()["kernels"]
                            if x["site"] in ("sparse_attn", "kv_index_pool",
                                             "index_select",
                                             "paged_decode", "paged_prefill",
                                             "kv_pool", "moe_decode",
                                             "moe_share")})}
    # the served twin goes before the reference comes: at the published
    # widths each is a few GB
    app._run_paged = inner
    del app, ad, inner
    gc.collect()
    forward = jax.jit(lambda w_, i_, control: ref.forward(
        hf, w_, i_, control=control, block=block), static_argnums=2)
    ends = seen.sum(axis=1)
    out["decode_positions"] = (ends - n).tolist()
    if not all(seen[r, :ends[r]].all() for r in range(rows)) \
            or not (fed[:, :n] == prompts).all():
        return dict(out, error="a row's served positions are not a prefix "
                               "of its tokens")

    def reference(control=None):
        # on the tokens the served path was FED (a row's own samples past
        # its prompt); positions past a row's end are padding, causal
        with jax.default_matmul_precision("highest"):
            return np.stack([np.asarray(forward(
                w, jnp.asarray(fed[r:r + 1]), control))[0]
                for r in range(rows)])

    def verdict(want):
        err = np.abs(got - want)
        ratio = (err / (gate["atol"] + gate["rtol"] * np.abs(want))).max(-1)
        at = np.arange(reach)[None, :]

        def part(where):
            x = ratio[where & seen]
            return dict(positions=int(x.size),
                        median_ratio=float(np.median(x)),
                        worst_ratio=float(x.max()),
                        held_share=float((x <= 1).mean())) if x.size else None
        return dict(
            all=part(at >= 0), under_topk=part(at < min(topk, n)),
            past_topk=part((at >= topk) & (at < n)), decode=part(at >= n),
            max_error=float(err[seen].max()),
            median_pos_error=float(np.median(err.max(-1)[seen])))
    out.update(verdict(reference()))
    out["controls"] = {c: verdict(reference(c)) for c in controls}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="keye-vl-2.0-30b-a3b")
    ap.add_argument("--seed", type=int, default=2147483750)
    ap.add_argument("--long", type=int, default=8192)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--controls", default="")
    ap.add_argument("--skip-gate", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from harness import build
    backend = jax.devices()[0].platform
    if backend == "cpu":
        from neuronx_distributed_inference_tpu.compat import \
            force_cpu_devices
        force_cpu_devices(1)
    cfg = build.load_json("configs", args.config + ".json")
    out = {"backend": backend, "seed": args.seed, "config": args.config}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"gate50-{backend}.json")

    def save():
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    if not args.skip_gate:
        out["gate"] = gate_and_controls(
            cfg, args.seed, args.controls.split(",") if args.controls
            else None)
        print(json.dumps(out["gate"], indent=1), flush=True)
        save()
    if args.long:
        out["long"] = long_walk(cfg, args.seed, args.long, rows=args.rows)
        print(json.dumps(out["long"], indent=1), flush=True)
    save()
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
