"""PR 67's own check of a ``ling_kda`` configuration (ISSUE 67, point 6b), on
whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``;
``tests/test_ling_kda_paged.py`` loads this file at a toy size, so it stays
runnable, ROADMAP C13), then the chip at the published widths.

1. ``scripts/gate56.py``'s :func:`gate_and_controls` on this configuration:
   the logit gate on the twin at the file's widths (``gate.config``: layers
   0-6, both dense layers, a linear layer behind an expert block, the latent
   layer and a linear layer AFTER it) and every fault of
   ``references/ling_kda.py`` ``CONTROLS``, then fp8-rounded weights, against
   the SAME served logits.
2. :func:`long_walk`: what the harness's gate (ONE full-batch window of 112
   tokens, 16 decode steps) cannot see: a matrix state and three conv tails
   CARRIED between chunks of 256 and steps, the one-row chunk program over
   sub-chunks of 16, the latent pool behind thousands of cached tokens, a
   slot and its pages re-used after release. ``scripts/gate56.py``'s walk
   (``rows`` prompts of ``tokens`` tokens through ``PagedEngineAdapter`` on
   the configuration AS THE FILE HAS IT, teacher-forced decode, one row
   released and a NEW prompt in its slot; every served position's logits
   against the reference's, a row and a layer at a time), and besides the
   FINAL STATES: every linear layer's state in the slots after the walk
   against the reference's after the same tokens (a sequence's slot is the
   one nearest its reference: the walk does not hand the slot map out).

    python3 scripts/gate67.py [--config ling-3.0-flash] [--seed n]
        [--long 2304] [--rows 4] [--new 32] [--second n] [--controls a,b]
        [--skip-gate] [--fp8] [--walk-twin file|gate] [--walk-layers n]

writes ``chiprun_out/gate67-<backend>-<seed>.json``. No timing is taken or
printed.
"""

import argparse
import functools
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: the embedding's published name and the untied head's
NAMES = ("model.embed_tokens.weight", "lm_head.weight")


@functools.lru_cache(maxsize=None)
def _gate56():
    spec = importlib.util.spec_from_file_location(
        "gate56", os.path.join(ROOT, "scripts", "gate56.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _head(ref, hf):
    """What the head reads: ``N(h_L; model.norm)``."""
    return lambda w, x: ref.rms_norm(x, w["model.norm.weight"],
                                     hf["rms_norm_eps"])


def _progress(what):
    """A line a stage, with the process's peak resident set: a walk that is
    ended for memory says where (the machine with one chip has 40 GiB)."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"[gate67] {what}; peak host memory {peak:.1f} GiB", flush=True)


def gate_twin_of(cfg, layers):
    """The published keys a twin of the first ``layers`` layers replaces: the
    depth, and the two limit lists cut in step with it."""
    return {"num_hidden_layers": layers,
            "expert_swiglu_limit_list":
                cfg["expert_swiglu_limit_list"][:layers],
            "share_expert_swiglu_limit_list":
                cfg["share_expert_swiglu_limit_list"][:layers]}


def long_walk(cfg, seed, tokens, rows=4, new_tokens=32, block=256,
              second=None, twin=None, served_precision=None):
    """See the module docstring. ``twin`` ``{}``: the file's own
    configuration; None: the gate's twin. Returns ``scripts/gate56.py``'s
    record with ``final_states`` added: per sequence still in a slot, the
    largest state error against the reference as a share of the largest
    reference value, and the same for a state rounded to bfloat16 (what one
    precision down reads)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    g56 = _gate56()
    kept, apps = {}, []

    def blocked_hidden(ref, hf, block_, head=_head, names=NAMES):
        """``scripts/gate56.py``'s, keeping every linear layer's last state
        by the sequence's ids."""
        def of_layer(i):
            def run(w, x):
                whole, ref.ATTEND_BLOCK = ref.ATTEND_BLOCK, block_
                try:
                    out = ref.layer(hf, w, i, x)
                finally:
                    ref.ATTEND_BLOCK = whole
                return out[0], out[2]
            return jax.jit(run)
        layers = [of_layer(i) for i in range(hf["num_hidden_layers"])]
        final = jax.jit(head(ref, hf))

        def hidden(w, ids):
            x = w[names[0]][jnp.asarray(ids)].astype(jnp.float32)
            states = []
            for layer in layers:
                x, state = layer(w, x)
                if state is not None:
                    states.append(np.asarray(state[0]))
            kept[np.asarray(ids).tobytes()] = np.stack(states)
            _progress(f"reference: a sequence of {np.shape(ids)[-1]} tokens")
            return final(w, x)
        return hidden

    inner_app = g56._served_app

    def served_app(*a, **kw):
        _progress("reference done; building the served application")
        apps.append(inner_app(*a, **kw))
        _progress("served application on the device")
        return apps[-1]
    whole_hidden, g56.blocked_hidden = g56.blocked_hidden, blocked_hidden
    g56._served_app = served_app
    try:
        out = g56.long_walk(cfg, seed, tokens, rows=rows,
                            new_tokens=new_tokens, block=block,
                            second=second, head=_head, names=NAMES,
                            twin=twin, served_precision=served_precision)
    finally:
        g56.blocked_hidden, g56._served_app = whole_hidden, inner_app
    # the sequences whose every token was fed and whose slot was not handed
    # on: all but the released one (and the short self-check's)
    slots = np.asarray(apps[-1].cache["ssm"])             # (Ls, slots, ...)
    lengths = {tokens + new_tokens,
               (second or max(tokens // 4, 1)) + new_tokens}
    readings = []
    for ids, want in kept.items():
        if len(ids) // 4 not in lengths:
            continue
        err = np.abs(slots - want[:, None]).reshape(
            slots.shape[0], slots.shape[1], -1).max(-1).max(0)
        rounded = np.abs(want.astype(jnp.bfloat16).astype(np.float32)
                         - want).max()
        readings.append(dict(
            tokens=len(ids) // 4, slot=int(err.argmin()),
            error_share=float(err.min() / np.abs(want).max()),
            other_slots_share=float(np.sort(err)[1] / np.abs(want).max())
            if err.size > 1 else None,
            bf16_rounding_share=float(rounded / np.abs(want).max())))
    # the released sequence's slot holds the late prompt's state: it reads
    # as far off as any other slot, and is left out of the verdict
    readings.sort(key=lambda r: r["error_share"])
    held = readings[:rows]
    out["final_states"] = dict(
        sequences=held, layers=int(slots.shape[0]),
        worst_share=max(r["error_share"] for r in held),
        finite=bool(np.isfinite(slots).all()))
    del apps[:]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ling-3.0-flash")
    ap.add_argument("--seed", type=int, default=2147483767)
    ap.add_argument("--long", type=int, default=2304)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--second", type=int, default=0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--skip-gate", action="store_true")
    ap.add_argument("--fp8", action="store_true",
                    help="the named controls, THEN fp8-rounded weights (the "
                         "default run does both after every control)")
    ap.add_argument("--walk-twin", choices=("file", "gate"), default="file")
    ap.add_argument("--walk-layers", type=int, default=0,
                    help="walk the first N layers of the file's configuration "
                         "(the 18-layer walk needs over 40 GiB of HOST memory "
                         "beside the reference: a machine with one chip has "
                         "40)")
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
    path = os.path.join(ROOT, "chiprun_out",
                        f"gate67-{backend}-{args.seed}.json")

    def save():
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    if not args.skip_gate:
        named = args.controls.split(",") if args.controls else None
        load = build.load_reference

        def cut(model_type):
            # the reference's list cut to the named ones: the run then ends
            # with the fp8 pair, as the full run does
            ref = load(model_type)
            ref.CONTROLS = tuple(named or ())
            return ref
        if args.fp8:
            build.load_reference = cut
        try:
            out["gate"] = _gate56().gate_and_controls(
                cfg, args.seed, None if args.fp8 else named)
        finally:
            build.load_reference = load
        print(json.dumps(out["gate"], indent=1), flush=True)
        save()
    if args.long:
        out["long"] = long_walk(
            cfg, args.seed, args.long, rows=args.rows, new_tokens=args.new,
            second=args.second or None,
            twin=gate_twin_of(cfg, args.walk_layers) if args.walk_layers
            else {} if args.walk_twin == "file" else None)
        out["long"]["layers"] = args.walk_layers or (
            cfg["num_hidden_layers"] if args.walk_twin == "file"
            else cfg["gate"]["config"]["num_hidden_layers"])
        print(json.dumps(out["long"], indent=1), flush=True)
    save()
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
