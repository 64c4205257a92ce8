"""PR 40's own check of a ``longcat_flash`` configuration (ISSUE 40, point 9),
on whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``),
then the chip. Loaded by ``tests/test_longcat_flash_paged.py`` at a toy size,
so it stays runnable (ROADMAP C13).

1. :func:`gate_and_controls`: the configuration's logit gate
   (``harness/build.py`` ``judge_gate`` on the twin at the file's widths) and
   every control that must fail it - the reference with one deliberate fault
   (``references/longcat_flash.py`` ``CONTROLS``) or on fp8-rounded weights -
   against the SAME served logits.
2. :func:`long_walk`: one comparison the harness's gate cannot make: a long
   prompt walked through ``PagedEngineAdapter`` in chunks of the widest
   bucket, then teacher-forced decode steps, logits against the reference
   with its attention computed a block of queries at a time, so that the
   absorbed decode path and the chunk-behind-prefix path are held at a
   context of many pages.

    python3 scripts/gate40.py [--config longcat-flash-omni] [--seed n]
        [--long 4096] [--controls a,b] [--skip-gate]

writes ``chiprun_out/gate40-<backend>.json``. No timing is taken or printed.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _setup(cfg):
    from harness import build
    twin = build.gate_overrides(cfg["gate"])
    hf = build.hf_config(cfg, twin)
    ref = build.load_reference(hf["model_type"])
    return twin, hf, ref, ref.weight_shapes(hf)


def _view(table, w, cfg):
    import numpy as np
    from harness import weights
    return weights.HfView(table, w, dtype=None if cfg["dtype"] == "bfloat16"
                          else np.dtype(cfg["dtype"]))


def _precision(served_precision):
    import contextlib
    import jax
    return (jax.default_matmul_precision(served_precision)
            if served_precision else contextlib.nullcontext())


def judge(cfg, got, want, margins, prompt_len):
    """``judge_gate``'s verdict of served logits against reference ones."""
    import numpy as np
    from harness import build
    gate = cfg["gate"]
    err = np.abs(got - want)
    ratio = (err / (gate["atol"] + gate["rtol"] * np.abs(want))).max(axis=-1)
    v = build.judge_gate(ratio, margins, prompt_len, gate)
    return dict(passed=v["passed"], median_ratio=round(v["median_ratio"], 3),
                worst_ratio=round(v["worst_ratio"], 3), held=v["held_share"],
                max_error=float(err.max()),
                median_pos_error=float(np.median(err.max(-1))), why=v["why"])


def gate_and_controls(cfg, seed, controls=None, served_precision=None):
    """The gate's verdict of the served twin (``sound``) and of the same
    served logits against the reference under each of ``controls`` (default:
    every one of the reference's ``CONTROLS``, then fp8-rounded weights)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build, weights
    twin, hf, ref, table = _setup(cfg)
    gate = cfg["gate"]
    b, s, n_new = gate["batch"], gate["prompt_len"], gate["new_tokens"]
    rng = np.random.default_rng([seed, 0x67617465])
    ids = rng.integers(1, hf["vocab_size"], size=(b, s + n_new),
                       dtype=np.int64).astype(np.int32)
    w = weights.make_weights(table, seed)
    bucket = -(-s // 32) * 32
    app = build.build_app(cfg, overrides=twin, output_logits=True,
                          serve=dict(cfg["serve"], batch_size=b,
                                     seq_len=2 * bucket, pa_num_blocks=4 * b,
                                     context_encoding_buckets=[bucket]))
    app._put_params(app.family.convert_hf_state_dict(_view(table, w, cfg),
                                                     app.spec))
    app.init_cache()
    with _precision(served_precision):
        steps = app.generate(ids[:, :s], max_new_tokens=n_new + 1,
                             return_logits=True,
                             teacher_tokens=ids[:, s:])["logits"]
    v = hf["vocab_size"]
    got = np.concatenate(
        [np.asarray(steps[0])[:, :s, :v]]
        + [np.asarray(x)[:, -1:, :v] for x in steps[1:n_new + 1]], axis=1)
    out = {"notes": [n for n in app.warmup_state()["kernels"]
                     if n["site"].startswith(("mla", "latent"))]}
    del app, steps
    gc.collect()

    def reference(weights_, control=None):
        with jax.default_matmul_precision("highest"):
            lg, mg = jax.jit(lambda w_, i_: ref.forward(
                hf, w_, i_, with_margins=True, control=control))(
                    weights_, jnp.asarray(ids))
        return np.asarray(lg), np.asarray(mg)
    want, margins = reference(w)
    out.update(logit_sigma=float(want.std()),
               logit_max=float(np.abs(want).max()),
               margin_quantiles=[float(q) for q in
                                 np.quantile(margins, [0.01, 0.1, 0.5])],
               sound=judge(cfg, got, want, margins, s), controls={})
    every = controls is None
    for control in (ref.CONTROLS if every else controls):
        wc, mc = reference(w, control)
        out["controls"][control] = judge(cfg, got, wc, mc, s)
    if every:
        w8 = {k: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
              for k, x in w.items()}
        w8_, m8 = reference(w8)
        out["controls"]["fp8_weights"] = judge(cfg, got, w8_, m8, s)
        # one precision down on the reference alone: fp8 against itself
        out["controls"]["fp8_weights_vs_reference"] = judge(
            cfg, w8_, want, margins, s)
    return out


def blocked_attend(ref, block=512):
    """The reference's ``attend`` with its scores computed ``block`` queries
    at a time, each over the keys up to its last (a 4096-token prompt's 64 x
    4096 x 4096 float32 scores do not fit beside the model): the same
    arithmetic in another order of evaluation."""
    import jax.numpy as jnp
    plain = ref.attend

    def attend(q_nope, q_rot, k_nope, k_rot, v, q_pos, k_pos):
        outs = []
        for lo in range(0, q_nope.shape[1], block):
            hi = lo + block
            outs.append(plain(q_nope[:, lo:hi], q_rot[:, lo:hi],
                              k_nope[:, :hi], k_rot[:, :hi], v[:, :hi],
                              q_pos[lo:hi], k_pos[:hi]))
        return jnp.concatenate(outs, axis=1)
    return attend


def long_walk(cfg, seed, tokens, new_tokens=16, served_precision=None):
    """A prompt of ``tokens`` walked through ``PagedEngineAdapter()`` in
    chunks, then ``new_tokens`` teacher-forced decode steps on the twin; every
    served position's logits against the reference's, by the gate's
    tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build, weights
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    twin, hf, ref, table = _setup(cfg)
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
    app._put_params(app.family.convert_hf_state_dict(_view(table, w, cfg),
                                                     app.spec))
    app.init_cache()
    got = {}
    inner = app._run_paged

    def tap(ids_, pos, slots, bt, last, *a, **kw):
        o = inner(ids_, pos, slots, bt, last, *a, **kw)
        lg = np.asarray(o["logits"])[0]
        for t in np.nonzero(np.asarray(slots)[0] >= 0)[0]:
            got[int(np.asarray(pos)[0, t])] = lg[t, :hf["vocab_size"]]
        return o
    app._run_paged = tap
    with _precision(served_precision):
        ad = PagedEngineAdapter(app)
        ad.add_requests([1], [ids[0, :n].tolist()])
        for k in range(new_tokens):
            # teacher-forced: feed the drawn ids, whatever was sampled
            ad.seqs[1].last_token = int(ids[0, n + k])
            ad.step([1])
    out = {"tokens": n, "positions_served": len(got),
           "notes": sorted({(x["site"], x["reason"])
                            for x in app.warmup_state()["kernels"]
                            if x["site"].startswith("mla")})}
    plain = ref.attend
    try:
        with jax.default_matmul_precision("highest"):
            short = jnp.asarray(ids[:, :96])
            a_ = np.asarray(jax.jit(lambda w_: ref.forward(hf, w_, short))(w))
            ref.attend = blocked_attend(ref, min(512, max(16, n // 4)))
            b_ = np.asarray(jax.jit(lambda w_: ref.forward(hf, w_, short))(w))
            out["blocked_vs_plain_reference"] = float(np.abs(a_ - b_).max())
            want = np.asarray(jax.jit(
                lambda w_: ref.forward(hf, w_, jnp.asarray(ids)))(w))[0]
    finally:
        ref.attend = plain
    missing = [p for p in range(n + new_tokens) if p not in got]
    if missing:
        return dict(out, missing_positions=missing[:8])
    err = np.abs(np.stack([got[p] for p in range(n + new_tokens)]) - want)
    ratio = (err / (gate["atol"] + gate["rtol"] * np.abs(want))).max(-1)
    width = max(cfg["serve"]["context_encoding_buckets"])
    out.update(
        median_ratio=float(np.median(ratio)), worst_ratio=float(ratio.max()),
        held_share=float((ratio <= 1).mean()),
        decode_median_ratio=float(np.median(ratio[n:])),
        decode_worst_ratio=float(ratio[n:].max()),
        first_chunk_median_ratio=float(np.median(ratio[:width])),
        last_chunk_median_ratio=float(np.median(ratio[max(0, n - width):n])),
        max_error=float(err.max()),
        median_pos_error=float(np.median(err.max(-1))))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="longcat-flash-omni")
    ap.add_argument("--seed", type=int, default=2147483740)
    ap.add_argument("--long", type=int, default=4096)
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
    if not args.skip_gate:
        out["gate"] = gate_and_controls(
            cfg, args.seed, args.controls.split(",") if args.controls
            else None)
        print(json.dumps(out["gate"], indent=1), flush=True)
    if args.long:
        out["long"] = long_walk(cfg, args.seed, args.long)
        print(json.dumps(out["long"], indent=1), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"gate40-{backend}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
