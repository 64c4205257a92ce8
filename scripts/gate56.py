"""PR 56's own check of a ``cohere2_moe`` configuration (ISSUE 56, point 6),
on whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``, a
toy size: ``tests/test_cohere2_moe_paged.py`` loads this file, so it stays
runnable, ROADMAP C13), then the chip at the published widths.

1. :func:`gate_and_controls`: the configuration's logit gate on the twin at
   the file's widths (``gate.config``: one whole period, the window shrunk to
   64 FOR THE TWIN so that 128 tokens a row cross it), and every control that
   must fail it - the reference with one deliberate fault
   (``references/cohere2_moe.py`` ``CONTROLS``) or on fp8-rounded weights -
   against the SAME served logits. ``scripts/gate40.py``'s with the order
   turned round: the twin IS the configuration (9.47 GB of weights), so the
   seeded weights and the served application are never on the device
   together - the served logits first, the application freed, then the
   weights drawn again from the seed for the reference and its controls.
2. :func:`long_walk` (``scripts/gate54.py``'s, on this reference): what the
   harness's gate of 128 tokens a row cannot see, at the PUBLISHED window.
   ``rows`` prompts of ``tokens`` tokens walked through
   ``PagedEngineAdapter`` with the configuration's own keywords (chunks of
   256: the rings of the window layers wrap, the full layer reads behind
   thousands of cached tokens), then ``new_tokens`` teacher-forced decode
   steps a row; one row is released and a NEW prompt takes its slot (its
   rings), walks its chunks beside the other rows' decode steps and decodes
   too. Every served position's logits against the reference's under
   ``jax.default_matmul_precision("highest")``: the reference runs first, a
   row and a layer at a time, its attention a block of queries at a time,
   and keeps what the head reads; each dispatch's logits are then held to
   the head of those rows ON THE DEVICE.

    python3 scripts/gate56.py [--config command-a-plus-05-2026] [--seed n]
        [--long 8192] [--rows 4] [--new 64] [--second n] [--controls a,b]
        [--skip-gate]

writes ``chiprun_out/gate56-<backend>.json``. No timing is taken or
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

#: the gate's twin shrinks these for its 128 tokens; the long walk runs the
#: twin's depth at the file's own (published) values
PUBLISHED_IN_THE_WALK = ("sliding_window",)


@functools.lru_cache(maxsize=None)
def _gate40():
    spec = importlib.util.spec_from_file_location(
        "gate40", os.path.join(ROOT, "scripts", "gate40.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _served_app(cfg, twin, table, seed, serve):
    """The twin's application with the seeded weights loaded through the
    family's own converter; the seeded arrays are off the device before the
    parameters go on."""
    from harness import build, weights
    g40 = _gate40()
    app = build.build_app(cfg, overrides=twin, output_logits=True,
                          serve=serve)
    w = weights.make_weights(table, seed)
    view = g40._view(table, w, cfg)
    del w
    host = app.family.convert_hf_state_dict(view, app.spec)
    del view
    gc.collect()
    app._put_params(host)
    del host
    return app.init_cache()


def gate_and_controls(cfg, seed, controls=None, served_precision=None):
    """The gate's verdict of the served twin (``sound``) and of the same
    served logits against the reference under each of ``controls`` (default:
    every one of the reference's ``CONTROLS``, then fp8-rounded weights)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import weights
    g40 = _gate40()
    twin, hf, ref, table = g40._setup(cfg)
    gate = cfg["gate"]
    b, s, n_new = gate["batch"], gate["prompt_len"], gate["new_tokens"]
    rng = np.random.default_rng([seed, 0x67617465])
    ids = rng.integers(1, hf["vocab_size"], size=(b, s + n_new),
                       dtype=np.int64).astype(np.int32)
    bucket = -(-s // 32) * 32
    app = _served_app(cfg, twin, table, seed, dict(
        cfg["serve"], batch_size=b, seq_len=2 * bucket, pa_num_blocks=4 * b,
        context_encoding_buckets=[bucket]))
    with g40._precision(served_precision):
        steps = app.generate(ids[:, :s], max_new_tokens=n_new + 1,
                             return_logits=True,
                             teacher_tokens=ids[:, s:])["logits"]
    v = hf["vocab_size"]
    got = np.concatenate(
        [np.asarray(steps[0])[:, :s, :v]]
        + [np.asarray(x)[:, -1:, :v] for x in steps[1:n_new + 1]], axis=1)
    out = {"notes": sorted({(x["site"], x["path"], x["reason"])
                            for x in app.warmup_state()["kernels"]})}
    del app, steps
    gc.collect()
    w = weights.make_weights(table, seed)

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
               error_quantiles_a_position=[float(q) for q in np.quantile(
                   np.abs(got - want).max(-1), [0.5, 0.95, 1.0])],
               sound=g40.judge(cfg, got, want, margins, s), controls={})
    print("sound", json.dumps(out["sound"]), flush=True)
    every = controls is None
    for control in (ref.CONTROLS if every else controls):
        wc, mc = reference(w, control)
        out["controls"][control] = g40.judge(cfg, got, wc, mc, s)
        print(control, json.dumps(out["controls"][control]), flush=True)
    if every:
        # a tensor at a time: the two sets do not fit the device together
        w8 = {k: w.pop(k).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
              for k in list(w)}
        w8_, m8 = reference(w8)
        out["controls"]["fp8_weights"] = g40.judge(cfg, got, w8_, m8, s)
        # one precision down on the reference alone: fp8 against itself
        out["controls"]["fp8_weights_vs_reference"] = g40.judge(
            cfg, w8_, want, margins, s)
    return out


def _cohere_head(ref, hf):
    """What the head reads of the last layer's output: this architecture's
    final norm (``long_walk``'s ``head`` for another one: scripts/gate61.py)."""
    return lambda w, x: ref.layer_norm(x, w["model.norm.weight"],
                                       hf["layer_norm_eps"])


#: the embedding's published name and the head's (None: the tied head, the
#: embedding again); an architecture with other names hands long_walk its own
TIED = ("model.embed_tokens.weight", None)


def blocked_hidden(ref, hf, block, head=_cohere_head, names=TIED):
    """``ids (1, S) -> ref.final_hidden`` of ONE sequence, a layer a program
    and its attention ``block`` queries at a time (128 heads x 8192 x 8192
    float32 scores are 34 GB an attention): the same arithmetic in another
    order of evaluation. The programs are built once and serve every
    sequence of a length."""
    import jax
    import jax.numpy as jnp

    def of_layer(i):
        def run(w, x):
            whole, ref.ATTEND_BLOCK = ref.ATTEND_BLOCK, block
            try:
                return ref.layer(hf, w, i, x)[0]
            finally:
                ref.ATTEND_BLOCK = whole
        return jax.jit(run)
    layers = [of_layer(i) for i in range(hf["num_hidden_layers"])]
    final = jax.jit(head(ref, hf))

    def hidden(w, ids):
        x = w[names[0]][jnp.asarray(ids)].astype(jnp.float32)
        for layer in layers:
            x = layer(w, x)
        return final(w, x)
    return hidden


def long_walk(cfg, seed, tokens, rows=4, new_tokens=64, block=128,
              served_precision=None, twin=None, second=None,
              head=_cohere_head, names=TIED):
    """See the module docstring. ``second``: the length of the prompt that
    takes the released row's slot (default: a quarter of ``tokens``; of
    ``tokens`` itself, the reference's programs serve it too). ``head``:
    the reference's final norm (:func:`_cohere_head`'s form), for a stack
    without a window too (``scripts/gate61.py``). ``names``: the embedding's
    and the head's published names (:data:`TIED`; ``scripts/gate64.py``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build, weights
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    g40 = _gate40()
    gate, n = cfg["gate"], tokens
    if twin is None:
        twin = {k: v for k, v in build.gate_overrides(gate).items()
                if k not in PUBLISHED_IN_THE_WALK}
    hf = build.hf_config(cfg, twin)
    ref = build.load_reference(hf["model_type"])
    table = ref.weight_shapes(hf)
    second = second or max(n // 4, 1)
    rng = np.random.default_rng([seed, 0x6c6f6e67])
    # sequence r < rows: a prompt of n; sequence rows: the late one. Each
    # row's whole teacher-forced stream is drawn up front
    lengths = [n + new_tokens] * rows + [second + new_tokens]
    prompt_len = [n] * rows + [second]
    streams = [rng.integers(1, hf["vocab_size"], size=m, dtype=np.int64
                            ).astype(np.int32) for m in lengths]
    w = weights.make_weights(table, seed)
    with jax.default_matmul_precision("highest"):
        short = streams[0][None, :min(96, n)]
        plain = jax.jit(lambda w_, i_: ref.final_hidden(hf, w_, i_)[0])(
            w, jnp.asarray(short))
        blocked = blocked_hidden(ref, hf, max(16, short.shape[1] // 4),
                                 head, names)(w, short)
        out = {"blocked_vs_plain_reference":
               float(jnp.abs(plain - blocked).max())}
        of = blocked_hidden(ref, hf, block, head, names)
        hidden = [np.asarray(of(w, s[None]))[0] for s in streams]
    embed = w[names[1] or names[0]]
    del w, plain, blocked
    gc.collect()
    bs = cfg["serve"]["pa_block_size"]
    width = max(cfg["serve"]["context_encoding_buckets"])
    blocks = -(-(n + new_tokens + 2 * bs) // bs)
    app = _served_app(cfg, twin, table, seed, dict(
        cfg["serve"], batch_size=rows, seq_len=blocks * bs,
        pa_num_blocks=rows * blocks + 8))
    vocab = hf["vocab_size"]
    atol, rtol = gate["atol"], gate["rtol"]
    scale = float(hf.get("logit_scale", 1.0))

    @jax.jit
    def held_to(logits, want_hidden):
        with jax.default_matmul_precision("highest"):
            want = jnp.einsum("th,vh->tv", want_hidden,
                              embed.astype(jnp.float32)) * scale
        err = jnp.abs(logits[:, :vocab] - want)
        return (err / (atol + rtol * jnp.abs(want))).max(-1), err.max(-1)

    ratio = [np.full((m,), np.nan, np.float32) for m in lengths]
    error = [np.zeros((m,), np.float32) for m in lengths]
    shapes, slots_of = set(), {}
    inner = app._run_paged

    def tap(ids_, pos, slots, bt, last, *a, **kw):
        # every logit the served path computes, held on the device to the
        # reference's for the sequence whose block table the row carries
        o = inner(ids_, pos, slots, bt, last, *a, **kw)
        shapes.add(tuple(np.shape(ids_)))
        owner = {blk[0]: sid for sid, blk in app.kv_mgr.tables.items()}
        ids_, pos = np.asarray(ids_), np.asarray(pos)
        slots, bt = np.asarray(slots), np.asarray(bt)
        for r in range(ids_.shape[0]):
            live = np.nonzero(slots[r] >= 0)[0]
            if not live.size:
                continue
            sid, at = owner[int(bt[r, 0])], pos[r, live]
            if not (ids_[r, live] == streams[sid][at]).all():
                raise AssertionError(f"sequence {sid} was fed other tokens "
                                     f"than its stream at {at[:4]}")
            got, err = held_to(o["logits"][r, live[0]:live[-1] + 1],
                               jnp.asarray(hidden[sid][at]))
            ratio[sid][at], error[sid][at] = np.asarray(got), np.asarray(err)
        return o
    app._run_paged = tap

    def teacher_force(ad):
        for sid, st in ad.seqs.items():
            if st.position < lengths[sid]:
                st.last_token = int(streams[sid][st.position])

    def done(sid):
        return not np.isnan(ratio[sid][-1])

    with g40._precision(served_precision):
        ad = PagedEngineAdapter(app, **cfg.get("adapter", {}))
        first = list(range(rows))
        ad.add_requests(first, [streams[r][:n].tolist() for r in first])
        # a deferred prefill walks a chunk before each step; a row whose
        # prompt is in decodes on, teacher-forced, beside the others' chunks
        while not all(done(r) for r in first):
            teacher_force(ad)
            ad.step([s for s in ad.seqs if not done(s)])
        slots_of.update({s: ad._state_slot.get(s) for s in first})
        # a row leaves; a NEW prompt takes its slot and its rings
        gone = rows // 2
        ad.release([gone])
        ad.add_requests([rows], [streams[rows][:second].tolist()])
        # (the rows that stay are dead rows of its decode steps)
        while not done(rows):
            teacher_force(ad)
            ad.step([s for s in ad.seqs if s == rows])
        slots_of[rows] = ad._state_slot.get(rows)
    ring = app.window_ring_pages or 0
    reach = hf.get("sliding_window") or 0
    out.update(
        tokens=n, rows=rows, new_tokens=new_tokens, second_prompt=second,
        window=reach, ring_pages=ring,
        ring_wraps=(n + new_tokens) // max(ring * bs, 1),
        program_shapes=sorted(shapes),
        released=gone, slot_reused=slots_of[rows] == slots_of[gone],
        host_stats={k: v for k, v in ad.host_stats.items()
                    if k.startswith(("kv_", "state_slot", "prefill_",
                                     "dispatches", "moe_"))},
        notes=sorted({(x["site"], x["path"], x["reason"])
                      for x in app.warmup_state()["kernels"]}))
    app._run_paged = inner
    del app, ad, inner
    gc.collect()
    missing = [(sid, int(np.isnan(r).sum())) for sid, r in enumerate(ratio)
               if np.isnan(r).any()]
    if missing:
        return dict(out, missing_positions=missing)

    def part(pieces):
        x = np.concatenate(pieces)
        return dict(positions=int(x.size), median_ratio=float(np.median(x)),
                    worst_ratio=float(x.max()),
                    held_share=float((x <= 1).mean()))
    prefill = [r[:p] for r, p in zip(ratio, prompt_len)]
    decode = [r[p:] for r, p in zip(ratio, prompt_len)]
    parts = dict(
        all=part(ratio), prefill=part(prefill), decode=part(decode),
        # the one chunk that starts from nothing, and the ones that continue
        first_chunk=part([r[:width] for r in ratio[:rows]]),
        later_chunks=part([r[min(width, n - 1):n] for r in ratio[:rows]]),
        last_chunk=part([r[max(0, n - width):n] for r in ratio[:rows]]),
        reused_slot=part([ratio[rows]]))
    if reach:
        # positions whose window has left the start behind: the ring has
        # been overwritten under them
        parts["past_window"] = part([r[reach:] for r in ratio])
    everything = np.concatenate(ratio)
    out.update(
        parts,
        positions_over_2=int((everything > 2).sum()),
        positions_over_4=int((everything > 4).sum()),
        # the gate's rules (1)-(3) over the walk's positions
        passed=bool(
            min(parts["prefill"]["held_share"], parts["decode"]["held_share"])
            >= gate.get("min_positions_held", 1.0)
            and np.median(everything) <= gate.get("median_ratio_max", 1.0)
            and everything.max() <= gate.get("worst_ratio_max", 1.0)),
        max_error=float(max(e.max() for e in error)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="command-a-plus-05-2026")
    ap.add_argument("--seed", type=int, default=2147483756)
    ap.add_argument("--long", type=int, default=8192)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--second", type=int, default=0)
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
    path = os.path.join(ROOT, "chiprun_out", f"gate56-{backend}.json")

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
        out["long"] = long_walk(cfg, args.seed, args.long, rows=args.rows,
                                new_tokens=args.new,
                                second=args.second or None)
        print(json.dumps(out["long"], indent=1), flush=True)
    save()
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
