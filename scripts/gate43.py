"""PR 43's own check of a ``smallthinker`` configuration (ISSUE 43), on
whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``), then
the chip. Loaded by ``tests/test_smallthinker_paged.py`` at a toy size, so it
stays runnable (ROADMAP C13).

1. ``gate_and_controls`` (``scripts/gate40.py``'s, which is generic): the
   configuration's logit gate on the twin at the file's widths, and every
   control that must fail it - the reference with one deliberate fault
   (``references/smallthinker.py`` ``CONTROLS``) or on fp8-rounded weights -
   against the SAME served logits. The twin's window is shrunk (the harness's
   gate holds 128 tokens a row and the published window is 4096).
2. :func:`long_walk`: what the harness's gate cannot reach: at the PUBLISHED
   window, a long prompt walked through ``PagedEngineAdapter`` in chunks of
   the widest bucket (the ring of the window layers wraps), then
   teacher-forced decode steps, every position's logits against the
   reference computed in blocks (its attention a block of queries at a time,
   its experts and its head a block of tokens at a time: the same arithmetic
   in another order of evaluation, held to the plain ``forward`` on a short
   prefix).

    python3 scripts/gate43.py [--config smallthinker-21b-a3b] [--seed n]
        [--long 8192] [--new 32] [--controls a,b] [--skip-gate]

writes ``chiprun_out/gate43-<backend>.json``. No timing is taken or printed.
"""

import argparse
import functools
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark"),
           os.path.join(ROOT, "scripts")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from gate40 import (_precision, _setup, _view,  # noqa: E402
                    gate_and_controls)

#: the gate's twin shrinks these for its 128 tokens; the long walk runs the
#: twin's depth at the file's own (published) values
PUBLISHED_IN_THE_WALK = ("sliding_window_size",)


def blocked_forward(ref, hf, w, ids, block):
    """``ref.forward``'s logits of ONE sequence ``ids`` (1, S), a generator
    of ``(lo, logits[lo:hi])``: the layer loop of ``forward`` with the
    attention a block of queries at a time (each over the keys it can see),
    the experts and the head a block of tokens at a time."""
    import jax
    import jax.numpy as jnp
    from harness.reference import L, linear, rms_norm
    eps, s = hf["rms_norm_eps"], ids.shape[1]
    window, rotary = ref.layouts(hf)
    plain = ref.attend

    def attend(q, k, v, q_pos, k_pos, reach):
        outs = []
        for lo in range(0, q.shape[1], block):
            hi = min(lo + block, q.shape[1])
            first = 0 if reach is None else max(0, lo - reach + 1)
            outs.append(plain(q[:, lo:hi], k[:, first:hi], v[:, first:hi],
                              q_pos[lo:hi], k_pos[first:hi], reach))
        return jnp.concatenate(outs, axis=1)

    def layer(i, w_, x):
        ref.attend = attend
        try:
            a = rms_norm(x, w_[L + "input_layernorm.weight"][i], eps)
            h = x + ref.attention(hf, w_, i, a, window[i], rotary[i])
            m = rms_norm(h, w_[L + "post_attention_layernorm.weight"][i], eps)
            y = jnp.concatenate(
                [ref.experts(hf, w_, i, a[:, lo:lo + block],
                             m[:, lo:lo + block])[0]
                 for lo in range(0, s, block)], axis=1)
            return h + y
        finally:
            ref.attend = plain

    head = jax.jit(lambda w_, x: linear(
        rms_norm(x, w_["model.norm.weight"], eps), w_["lm_head.weight"]))
    x = w["model.embed_tokens.weight"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(hf["num_hidden_layers"]):
        x = jax.jit(functools.partial(layer, i))(w, x)
    for lo in range(0, s, block):
        yield lo, head(w, x[:, lo:lo + block])[0]


def long_walk(cfg, seed, tokens, new_tokens=32, served_precision=None,
              block=None):
    """A prompt of ``tokens`` walked through ``PagedEngineAdapter()`` in
    chunks, then ``new_tokens`` teacher-forced decode steps, on the twin's
    depth at the PUBLISHED window; every served position's logits against
    the reference's, by the gate's tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build, weights
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    twin, _, ref, _ = _setup(cfg)
    twin = {k: v for k, v in twin.items() if k not in PUBLISHED_IN_THE_WALK}
    hf = build.hf_config(cfg, twin)
    table = ref.weight_shapes(hf)
    gate, n = cfg["gate"], tokens
    total = n + new_tokens
    rng = np.random.default_rng([seed, 0x6c6f6e67])
    ids = rng.integers(1, hf["vocab_size"], size=(1, total),
                       dtype=np.int64).astype(np.int32)
    w = weights.make_weights(table, seed)
    bs = cfg["serve"]["pa_block_size"]
    blocks = -(-(total + 2 * bs) // bs)
    app = build.build_app(cfg, overrides=twin, output_logits=True,
                          serve=dict(cfg["serve"], batch_size=4,
                                     seq_len=blocks * bs,
                                     pa_num_blocks=blocks + 8))
    app._put_params(app.family.convert_hf_state_dict(_view(table, w, cfg),
                                                     app.spec))
    app.init_cache()
    vocab = hf["vocab_size"]
    got = np.zeros((total, vocab), np.float32)
    seen = np.zeros((total,), bool)
    inner = app._run_paged

    def tap(ids_, pos, slots, bt, last, *a, **kw):
        o = inner(ids_, pos, slots, bt, last, *a, **kw)
        pos, slots = np.asarray(pos), np.asarray(slots)
        # the one live row: a chunk's only row, or its slot of a decode step
        for r in np.nonzero((slots >= 0).any(axis=1))[0]:
            live = np.nonzero(slots[r] >= 0)[0]
            lg = np.asarray(o["logits"][r, live[0]:live[-1] + 1, :vocab])
            got[pos[r, live]] = lg
            seen[pos[r, live]] = True
        return o
    app._run_paged = tap
    with _precision(served_precision):
        ad = PagedEngineAdapter(app, **cfg.get("adapter", {}))
        first = ad.add_requests([1], [ids[0, :n].tolist()])
        while 1 not in first and 1 not in ad.seqs:
            ad.step()                  # a deferred prefill: chunk by chunk
        for k in range(new_tokens):
            # teacher-forced: feed the drawn ids, whatever was sampled
            ad.seqs[1].last_token = int(ids[0, n + k])
            ad.step([1])
    ring = app.window_ring_pages
    out = {"tokens": n, "new_tokens": new_tokens,
           "positions_served": int(seen.sum()),
           "window": hf["sliding_window_size"], "ring_pages": ring,
           "ring_wraps": total // max(ring * bs, 1),
           "host_stats": {k: v for k, v in ad.host_stats.items()
                          if k.startswith(("kv_", "state_slot",
                                           "prefill_dispatches"))},
           "notes": sorted({(x["site"], x["reason"])
                            for x in app.warmup_state()["kernels"]})}
    del app, ad, inner
    gc.collect()
    if not seen.all():
        return dict(out, missing_positions=np.nonzero(~seen)[0][:8].tolist())
    block = block or min(512, max(16, n // 4))
    with jax.default_matmul_precision("highest"):
        short = ids[:, :min(96, total)]
        plain = np.asarray(jax.jit(
            lambda w_: ref.forward(hf, w_, jnp.asarray(short)))(w))[0]
        blocked = np.concatenate([np.asarray(lg) for _, lg in blocked_forward(
            ref, hf, w, short, max(16, short.shape[1] // 4))])
        out["blocked_vs_plain_reference"] = float(np.abs(plain - blocked).max())
        ratio = np.zeros((total,), np.float32)
        worst_err = 0.0
        for lo, want in blocked_forward(ref, hf, w, ids, block):
            want = np.asarray(want)
            err = np.abs(got[lo:lo + want.shape[0]] - want)
            worst_err = max(worst_err, float(err.max()))
            ratio[lo:lo + want.shape[0]] = (
                err / (gate["atol"] + gate["rtol"] * np.abs(want))).max(-1)
    width = max(cfg["serve"]["context_encoding_buckets"])
    reach = hf["sliding_window_size"]
    held = {"prefill": float((ratio[:n] <= 1).mean()),
            "decode": float((ratio[n:] <= 1).mean())}
    worst_at = np.argsort(ratio)[::-1][:8]
    out.update(
        # the gate's rules (1)-(3) over the walk's positions (rule (4), the
        # near-tie excuse, needs the reference's margins and is not taken)
        passed=bool(min(held.values()) >= gate.get("min_positions_held", 1.0)
                    and np.median(ratio) <= gate.get("median_ratio_max", 1.0)
                    and ratio.max() <= gate.get("worst_ratio_max", 1.0)),
        held=held,
        worst_positions=[(int(p), round(float(ratio[p]), 3))
                         for p in worst_at],
        positions_over_2=int((ratio > 2).sum()),
        positions_over_4=int((ratio > 4).sum()),
        positions_over_6=int((ratio > 6).sum()),
        median_ratio=float(np.median(ratio)), worst_ratio=float(ratio.max()),
        held_share=float((ratio <= 1).mean()),
        decode_median_ratio=float(np.median(ratio[n:])),
        decode_worst_ratio=float(ratio[n:].max()),
        first_chunk_median_ratio=float(np.median(ratio[:width])),
        last_chunk_median_ratio=float(np.median(ratio[max(0, n - width):n])),
        # positions whose window has left the start behind: the ring has
        # been overwritten under them
        past_window_median_ratio=(float(np.median(ratio[reach:]))
                                  if total > reach else None),
        max_error=worst_err)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="smallthinker-21b-a3b")
    ap.add_argument("--seed", type=int, default=2147483743)
    ap.add_argument("--long", type=int, default=8192)
    ap.add_argument("--new", type=int, default=32)
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
        out["long"] = long_walk(cfg, args.seed, args.long, args.new)
        print(json.dumps(out["long"], indent=1), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"gate43-{backend}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
