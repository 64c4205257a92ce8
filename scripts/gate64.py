"""PR 64's own check of a ``nemotron_h`` configuration (ISSUE 64, point 6b),
on whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``;
``tests/test_nemotron_h_paged.py`` loads this file at a toy size, so it stays
runnable, ROADMAP C13), then the chip at the published widths.

1. ``scripts/gate56.py``'s :func:`gate_and_controls` on this configuration:
   the logit gate on the twin at the file's widths (``gate.config``: a prefix
   of the published pattern with every kind of layer) and every fault of
   ``references/nemotron_h.py`` ``CONTROLS``, then fp8-rounded weights,
   against the SAME served logits.
2. :func:`long_walk`: what the harness's gate (ONE full-batch window of 112
   tokens) cannot see, a state and a conv tail CARRIED between chunks and
   steps at 8 B / C groups. ``rows`` prompts of ``tokens`` tokens walked
   through ``PagedEngineAdapter`` with the configuration's own keywords and
   chunk buckets on the configuration AS THE FILE HAS IT (all 52 layers;
   ``--walk-twin gate`` walks the gate's twin instead), then ``new_tokens``
   teacher-forced decode steps a row; one row is released and a NEW prompt
   takes its slot (and the stale state in it), walks its chunks beside the
   other rows' decode steps and decodes too. Every served position's logits
   against the reference's under ``jax.default_matmul_precision("highest")``,
   the reference a row and a layer at a time.

    python3 scripts/gate64.py [--config nemotron-3-nano-30b-a3b] [--seed n]
        [--long 2304] [--rows 4] [--new 32] [--second n] [--controls a,b]
        [--skip-gate] [--walk-twin file|gate] [--walk-layers n]

writes ``chiprun_out/gate64-<backend>-<seed>.json``. No timing is taken or
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
NAMES = ("backbone.embeddings.weight", "lm_head.weight")


@functools.lru_cache(maxsize=None)
def _gate56():
    spec = importlib.util.spec_from_file_location(
        "gate56", os.path.join(ROOT, "scripts", "gate56.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _head(ref, hf):
    """What the head reads: ``N(h_L; norm_f)``."""
    return lambda w, x: ref.rms_norm(x, w["backbone.norm_f.weight"],
                                     hf["layer_norm_epsilon"])


def long_walk(cfg, seed, tokens, rows=4, new_tokens=32, block=256,
              second=None, twin=None):
    """See the module docstring: ``scripts/gate56.py``'s walk (the rows, the
    release and the re-used slot, the comparison on the device) on this
    architecture's reference and names. ``twin`` ``{}``: the file's own
    configuration; None: the gate's twin."""
    return _gate56().long_walk(cfg, seed, tokens, rows=rows,
                               new_tokens=new_tokens, block=block,
                               second=second, head=_head, names=NAMES,
                               twin=twin)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="nemotron-3-nano-30b-a3b")
    ap.add_argument("--seed", type=int, default=2147483764)
    ap.add_argument("--long", type=int, default=2304)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--second", type=int, default=0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--skip-gate", action="store_true")
    ap.add_argument("--walk-twin", choices=("file", "gate"), default="file")
    ap.add_argument("--walk-layers", type=int, default=0,
                    help="walk the first N layers of the published pattern")
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
    path = os.path.join(
        ROOT, "chiprun_out", f"gate64-{backend}-{args.seed}"
        + (f"-{args.walk_layers}" if args.walk_layers else "") + ".json")

    def save():
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    if not args.skip_gate:
        out["gate"] = _gate56().gate_and_controls(
            cfg, args.seed, args.controls.split(",") if args.controls
            else None)
        print(json.dumps(out["gate"], indent=1), flush=True)
        save()
    if args.long:
        out["long"] = long_walk(
            cfg, args.seed, args.long, rows=args.rows, new_tokens=args.new,
            second=args.second or None,
            twin={"num_hidden_layers": args.walk_layers,
                  "hybrid_override_pattern":
                  cfg["hybrid_override_pattern"][:args.walk_layers]}
            if args.walk_layers else {} if args.walk_twin == "file" else None)
        out["long"]["layers"] = args.walk_layers or (
            cfg["num_hidden_layers"] if args.walk_twin == "file"
            else cfg["gate"]["config"]["num_hidden_layers"])
        print(json.dumps(out["long"], indent=1), flush=True)
    save()
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
