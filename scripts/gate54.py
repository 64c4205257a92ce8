"""PR 54's own check of a ``phi4flash`` configuration (ISSUE 54, point 5), on
whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``), then
the chip. Loaded by ``tests/test_phi4flash_paged.py`` at a toy size, so it
stays runnable (ROADMAP C13).

1. ``scripts/gate40.py``'s :func:`gate_and_controls`, which reads nothing of
   one architecture: the configuration's logit gate on the twin at the file's
   widths (``gate.config``: eight layers, every kind of layer; the window
   shrunk to 64 FOR THE TWIN so that 128 tokens a row cross it), and every
   control that must fail it - the reference with one deliberate fault
   (``references/phi4flash.py`` ``CONTROLS``) or on fp8-rounded weights -
   against the SAME served logits.
2. :func:`long_walk`: what the harness's gate of 128 tokens a row cannot
   see, at the PUBLISHED window and through the SERVED chunk form (ISSUE
   59; the gate's full-batch prefill hands out every position's logits and
   so walks the whole stack). ``rows`` prompts of ``tokens`` tokens walked
   through ``PagedEngineAdapter`` with the configuration's own keywords in
   chunks of 256 on the program the benchmark's cells run (``output_logits``
   off: the walk stops before the second decoder, which runs for the ONE
   token a prompt that is sampled from, and not at all in a chunk that is
   not the prompt's last): the ring of the window layers wraps, the shared
   pool is read behind thousands of cached tokens, the Mamba-1 state and
   tail are carried from chunk to chunk. Then ``new_tokens`` teacher-forced
   decode steps a row on the program that hands out every logit; one row is
   released and a NEW prompt takes its slot (its ring and its state), walks
   its chunks beside the other rows' decode steps and decodes too. What is
   held to the reference, under ``jax.default_matmul_precision("highest")``:
   every decode position's logits (they read every cache and state the
   chunks wrote) and each prompt's FIRST token, which must be a word whose
   reference logit lies within two tolerances of the reference's largest.
   The reference runs first, a row at a time and its attention a block of
   queries at a time, and keeps what the head reads; each step's logits are
   then held to the head of those rows ON THE DEVICE (a row's logits over
   200,064 words do not fit the host whole).

    python3 scripts/gate54.py [--config phi-4-mini-flash-reasoning]
        [--seed n] [--long 8192] [--rows 4] [--new 64] [--controls a,b]
        [--skip-gate]

writes ``chiprun_out/gate54-<backend>.json``. No timing is taken or
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


def gate_and_controls(cfg, seed, controls=None, served_precision=None):
    """:func:`gate40.gate_and_controls` of ``cfg``."""
    return _gate40().gate_and_controls(cfg, seed, controls, served_precision)


def blocked_hidden(ref, hf, w, ids, block):
    """``ref.final_hidden`` of ONE sequence ``ids`` (1, S) with every softmax
    attention a block of queries at a time (20 heads x 8192 x 8192 float32
    scores are 5.4 GB an attention): the same arithmetic in another order."""
    import jax
    import jax.numpy as jnp
    plain = ref.softmax_attention

    def attention(q, k, v, mask, scale):
        return jnp.concatenate(
            [plain(q[:, lo:lo + block], k[:, :lo + block], v[:, :lo + block],
                   mask[lo:lo + block, :lo + block], scale)
             for lo in range(0, q.shape[1], block)], axis=1)
    ref.softmax_attention = attention
    try:
        return jax.jit(lambda w_, i_: ref.final_hidden(hf, w_, i_))(
            w, jnp.asarray(ids))
    finally:
        ref.softmax_attention = plain


def long_walk(cfg, seed, tokens, rows=4, new_tokens=64, block=512,
              served_precision=None, twin=None, second=None):
    """See the module docstring. ``second``: the length of the prompt that
    takes the released row's slot (default: a quarter of ``tokens``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import build, weights
    from neuronx_distributed_inference_tpu.models import model_base
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
        plain = jax.jit(lambda w_, i_: ref.final_hidden(hf, w_, i_))(
            w, jnp.asarray(short))
        blocked = blocked_hidden(ref, hf, w, short,
                                 max(16, short.shape[1] // 4))
        out = {"blocked_vs_plain_reference":
               float(jnp.abs(plain - blocked).max())}
        hidden = [np.asarray(blocked_hidden(ref, hf, w, s[None], block))[0]
                  for s in streams]
    embed = w["model.embed_tokens.weight"]
    del w, plain, blocked
    gc.collect()
    bs = cfg["serve"]["pa_block_size"]
    width = max(cfg["serve"]["context_encoding_buckets"])
    blocks = -(-(n + new_tokens + 2 * bs) // bs)
    app = build.build_app(cfg, overrides=twin, output_logits=True,
                          serve=dict(cfg["serve"], batch_size=rows,
                                     seq_len=blocks * bs,
                                     pa_num_blocks=rows * blocks + 8))
    w = weights.make_weights(table, seed)
    app._put_params(app.family.convert_hf_state_dict(
        g40._view(table, w, cfg), app.spec))
    del w
    app.init_cache()
    vocab = hf["vocab_size"]
    atol, rtol = gate["atol"], gate["rtol"]

    # a chunk (T > 1) runs the program the benchmark's cells run, which hands
    # out tokens alone; a decode step the one that hands out every logit
    every_logit = app.get_compiled("paged_forward")
    served = jax.jit(functools.partial(
        model_base.paged_forward_step, app.spec,
        model_base.replace_output_logits(app.tpu_config)),
        donate_argnums=(1,))

    @jax.jit
    def held_to(logits, want_hidden):
        with jax.default_matmul_precision("highest"):
            want = jnp.einsum("th,vh->tv", want_hidden,
                              embed.astype(jnp.float32))
        err = jnp.abs(logits[:, :vocab] - want)
        return (err / (atol + rtol * jnp.abs(want))).max(-1), err.max(-1)

    @jax.jit
    def short_of_the_best(token, want_hidden):
        # the reference's margin over the served token, in tolerances
        with jax.default_matmul_precision("highest"):
            want = embed.astype(jnp.float32) @ want_hidden
        return (want.max() - want[token]) / (atol + rtol * jnp.abs(want.max()))

    # the decode positions of each sequence, and its first token's margin
    ratio = [np.full((new_tokens,), np.nan, np.float32) for _ in lengths]
    error = [np.zeros((new_tokens,), np.float32) for _ in lengths]
    first = [np.nan] * len(lengths)
    shapes, slots_of = set(), {}
    inner = app._run_paged

    def tap(ids_, pos, slots, bt, last, *a, **kw):
        chunk = np.shape(ids_)[1] > 1
        app._compiled[("paged_forward", 0)] = served if chunk else every_logit
        o = inner(ids_, pos, slots, bt, last, *a, **kw)
        shapes.add(tuple(np.shape(ids_)))
        owner = {blk[0]: sid for sid, blk in app.kv_mgr.tables.items()}
        ids_, pos, last = np.asarray(ids_), np.asarray(pos), np.asarray(last)
        slots, bt = np.asarray(slots), np.asarray(bt)
        for r in range(ids_.shape[0]):
            live = np.nonzero(slots[r] >= 0)[0]
            if not live.size:
                continue
            sid, at = owner[int(bt[r, 0])], pos[r, live]
            if not (ids_[r, live] == streams[sid][at]).all():
                raise AssertionError(f"sequence {sid} was fed other tokens "
                                     f"than its stream at {at[:4]}")
            if not chunk:
                # every logit of a decode step, held on the device to the
                # reference's for the sequence whose table the row carries
                got, err = held_to(o["logits"][r, live[0]:live[-1] + 1],
                                   jnp.asarray(hidden[sid][at]))
                at = at - prompt_len[sid]
                ratio[sid][at], error[sid][at] = (np.asarray(got),
                                                  np.asarray(err))
            elif last[r] >= 0:
                # a prompt's last chunk: its ONE sampled token
                assert "logits" not in o and pos[r, last[r]] == at[-1] \
                    == prompt_len[sid] - 1
                first[sid] = float(short_of_the_best(
                    o["tokens"][r], jnp.asarray(hidden[sid][at[-1]])))
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
        early = list(range(rows))
        ad.add_requests(early, [streams[r][:n].tolist() for r in early])
        # a deferred prefill walks a chunk before each step; a row whose
        # prompt is in decodes on, teacher-forced, beside the others' chunks
        while not all(done(r) for r in early):
            teacher_force(ad)
            ad.step([s for s in ad.seqs if not done(s)])
        slots_of.update({s: ad._state_slot.get(s) for s in early})
        # a row leaves; a NEW prompt takes its slot, its ring and its state
        gone = rows // 2
        ad.release([gone])
        ad.add_requests([rows], [streams[rows][:second].tolist()])
        # (the rows that stay are dead rows of its decode steps)
        while not done(rows):
            teacher_force(ad)
            ad.step([s for s in ad.seqs if s == rows])
        slots_of[rows] = ad._state_slot.get(rows)
    ring = app.window_ring_pages
    out.update(
        tokens=n, rows=rows, new_tokens=new_tokens, second_prompt=second,
        window=hf["sliding_window"], ring_pages=ring,
        ring_wraps=(n + new_tokens) // max(ring * bs, 1),
        program_shapes=sorted(shapes),
        released=gone, slot_reused=slots_of[rows] == slots_of[gone],
        host_stats={k: v for k, v in ad.host_stats.items()
                    if k.startswith(("kv_", "state_slot", "prefill_",
                                     "dispatches"))},
        notes=sorted({(x["site"], x["path"], x["reason"])
                      for x in app.warmup_state()["kernels"]}))
    app._run_paged = inner
    del app, ad, inner
    gc.collect()
    missing = [(sid, int(np.isnan(r).sum()) + int(np.isnan(first[sid])))
               for sid, r in enumerate(ratio)
               if np.isnan(r).any() or np.isnan(first[sid])]
    if missing:
        return dict(out, missing_positions=missing)

    def part(pieces):
        x = np.concatenate(pieces)
        return dict(positions=int(x.size), median_ratio=float(np.median(x)),
                    worst_ratio=float(x.max()),
                    held_share=float((x <= 1).mean()))
    parts = dict(
        # every decode position: each reads what the served chunks wrote
        decode=part(ratio), long_prompts=part(ratio[:rows]),
        reused_slot=part([ratio[rows]]))
    everything = np.concatenate(ratio)
    out.update(
        parts,
        # a first token may differ from the reference's by a near tie: its
        # reference logit within two tolerances of the largest
        first_tokens=dict(prompts=len(first), worst_margin=float(max(first)),
                          held=int(sum(m <= 2 for m in first))),
        # the gate's rules (1)-(3) over the walk's decode positions, and
        # every prompt's first token
        passed=bool(
            parts["decode"]["held_share"]
            >= gate.get("min_positions_held", 1.0)
            and np.median(everything) <= gate.get("median_ratio_max", 1.0)
            and everything.max() <= gate.get("worst_ratio_max", 1.0)
            and max(first) <= 2),
        max_error=float(max(e.max() for e in error)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="phi-4-mini-flash-reasoning")
    ap.add_argument("--seed", type=int, default=2147483754)
    ap.add_argument("--long", type=int, default=8192)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--new", type=int, default=64)
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
    path = os.path.join(ROOT, "chiprun_out", f"gate54-{backend}.json")

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
                                new_tokens=args.new)
        print(json.dumps(out["long"], indent=1), flush=True)
    save()
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
