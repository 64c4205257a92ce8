"""PR 47's own check of a ``deepseek_v3`` configuration (ISSUE 47, point 6),
on whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``),
then the chip. Loaded by ``tests/test_deepseek_v3_paged.py`` at a toy size,
so it stays runnable (ROADMAP C13).

1. ``scripts/gate40.py``'s :func:`gate_and_controls`, which reads nothing of
   one architecture: the configuration's logit gate on the twin at the file's
   widths, and every control that must fail it - the reference with one
   deliberate fault (``references/deepseek_v3.py`` ``CONTROLS``) or on
   fp8-rounded weights - against the SAME served logits.
2. :func:`long_walk`: what the harness's gate of 128 tokens a row cannot
   see. A prompt of at least 8192 tokens walked through
   ``PagedEngineAdapter`` in chunks of the widest bucket (each behind the
   prefix the earlier ones cached: both MLA prefill forms, the expert walk
   over a 256-row chunk), then teacher-forced decode steps (the absorbed
   kernel over hundreds of pages), every position's logits against the
   reference's: yarn's blended frequencies and ``mscale^2`` past the
   original 4096 positions. The controls that only a long row can fail
   (:data:`LONG_CONTROLS`) are judged against the same served logits.

    python3 scripts/gate47.py [--config deepseek-v3] [--seed n]
        [--long 8192] [--controls a,b] [--skip-gate]

writes ``chiprun_out/gate47-<backend>.json``. No timing is taken or printed.
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

#: faults of the rotary embedding: the long walk judges them where they bite
LONG_CONTROLS = ("no_yarn", "no_mscale", "rope_halves")


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


def long_walk(cfg, seed, tokens, new_tokens=16, served_precision=None,
              controls=LONG_CONTROLS):
    """A prompt of ``tokens`` walked through ``PagedEngineAdapter()`` in
    chunks, then ``new_tokens`` teacher-forced decode steps on the twin; every
    served position's logits against the reference's, by the gate's
    tolerance, and against the reference under each of ``controls``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build, weights
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    g40 = _gate40()
    twin, hf, ref, table = g40._setup(cfg)
    gate, n = cfg["gate"], tokens
    rng = np.random.default_rng([seed, 0x6c6f6e67])
    ids = rng.integers(1, hf["vocab_size"], size=(1, n + new_tokens),
                       dtype=np.int64).astype(np.int32)
    w = weights.make_weights(table, seed)
    bs = cfg["serve"]["pa_block_size"]
    blocks = -(-(n + new_tokens + 2 * bs) // bs)
    app = build.build_app(cfg, overrides=twin, output_logits=True,
                          serve=dict(cfg["serve"], batch_size=4,
                                     seq_len=blocks * bs,
                                     pa_num_blocks=blocks + 8))
    app._put_params(app.family.convert_hf_state_dict(
        g40._view(table, w, cfg), app.spec))
    app.init_cache()
    vocab = hf["vocab_size"]
    got = np.zeros((n + new_tokens, vocab), np.float32)
    seen = np.zeros((n + new_tokens,), bool)
    inner = app._run_paged

    def tap(ids_, pos, slots, bt, last, *a, **kw):
        o = inner(ids_, pos, slots, bt, last, *a, **kw)
        live = np.nonzero(np.asarray(slots)[0] >= 0)[0]
        at = np.asarray(pos)[0, live]
        got[at] = np.asarray(o["logits"])[0][live, :vocab]
        seen[at] = True
        return o
    app._run_paged = tap
    with g40._precision(served_precision):
        # the default adapter: the whole prompt inside one admission
        ad = PagedEngineAdapter(app)
        ad.add_requests([1], [ids[0, :n].tolist()])
        for k in range(new_tokens):
            # teacher-forced: feed the drawn ids, whatever was sampled
            ad.seqs[1].last_token = int(ids[0, n + k])
            ad.step([1])
    out = {"tokens": n, "positions_served": int(seen.sum()),
           "original_max_position_embeddings": (hf.get("rope_scaling") or {}
                                                ).get(
               "original_max_position_embeddings"),
           "notes": sorted({(x["site"], x["path"], x["reason"])
                            for x in app.warmup_state()["kernels"]
                            if x["site"].startswith(("mla", "moe"))})}
    # the served twin goes before the reference comes: at the published
    # widths each is a few GB
    app._run_paged = inner
    del app, ad, inner
    gc.collect()
    if not seen.all():
        return dict(out, missing_positions=np.nonzero(~seen)[0][:8].tolist())

    def reference(control=None):
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda w_: ref.forward(
                hf, w_, jnp.asarray(ids), control=control))(w))[0]

    def verdict(want):
        err = np.abs(got - want)
        ratio = (err / (gate["atol"] + gate["rtol"] * np.abs(want))).max(-1)
        width = max(cfg["serve"]["context_encoding_buckets"])
        past = out["original_max_position_embeddings"] or 0
        return dict(
            median_ratio=float(np.median(ratio)),
            worst_ratio=float(ratio.max()),
            held_share=float((ratio <= 1).mean()),
            held_share_past_original=(float((ratio[past:] <= 1).mean())
                                      if past < len(ratio) else None),
            decode_median_ratio=float(np.median(ratio[n:])),
            decode_worst_ratio=float(ratio[n:].max()),
            first_chunk_median_ratio=float(np.median(ratio[:width])),
            last_chunk_median_ratio=float(
                np.median(ratio[max(0, n - width):n])),
            max_error=float(err.max()),
            median_pos_error=float(np.median(err.max(-1))))
    out.update(verdict(reference()))
    out["controls"] = {c: verdict(reference(c)) for c in controls}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="deepseek-v3")
    ap.add_argument("--seed", type=int, default=2147483747)
    ap.add_argument("--long", type=int, default=8192)
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
    path = os.path.join(ROOT, "chiprun_out", f"gate47-{backend}.json")

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
        out["long"] = long_walk(cfg, args.seed, args.long)
        print(json.dumps(out["long"], indent=1), flush=True)
    save()
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
