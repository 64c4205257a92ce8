"""PR 61's own check of an ``lfm2_moe`` configuration (ISSUE 61, point 5),
on whatever backend the process has: the CPU first (``JAX_PLATFORMS=cpu``;
``tests/test_lfm2_moe_paged.py`` loads this file at a toy size, so it stays
runnable, ROADMAP C13), then the chip at the published widths.

1. ``scripts/gate56.py``'s :func:`gate_and_controls` on this configuration:
   the logit gate on the twin at the file's widths (``gate.config``: both
   dense layers and one whole period) and every fault of
   ``references/lfm2_moe.py`` ``CONTROLS``, then fp8-rounded weights,
   against the SAME served logits.
2. :func:`long_walk`: what the harness's gate (ONE full-batch window of 112
   tokens) cannot see, a CARRIED conv tail. ``rows`` prompts of ``tokens``
   tokens walked through ``PagedEngineAdapter`` with the configuration's own
   keywords and chunk buckets (every chunk but a prompt's first continues
   the tails the chunk before it left), then ``new_tokens`` teacher-forced
   decode steps a row; one row is released and a NEW prompt takes its slot
   (and the stale tails in it), walks its chunks beside the other rows'
   decode steps and decodes too. Every served position's logits against the
   reference's under ``jax.default_matmul_precision("highest")``: the
   reference runs first, a row and a layer at a time, and keeps what the
   head reads; each dispatch's logits are then held to the head of those
   rows ON THE DEVICE. ``--break zero_tail|padded_tail`` runs the walk with
   one of the two faults of the carry switched on in the program (the
   controls no reference can stand in for): it must NOT pass.

    python3 scripts/gate61.py [--config lfm2-8b-a1b] [--seed n] [--long 2304]
        [--rows 4] [--new 32] [--second n] [--controls a,b] [--skip-gate]
        [--break zero_tail]

writes ``chiprun_out/gate61-<backend>-<seed>[-<break>].json``. No timing is taken
or printed.
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


@functools.lru_cache(maxsize=None)
def _gate56():
    spec = importlib.util.spec_from_file_location(
        "gate56", os.path.join(ROOT, "scripts", "gate56.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def carry_fault(how):
    """One fault of the carried tail as ``(name in modules/ssm.py, its faulty
    stand-in)``: ``zero_tail``, every chunk starts from zeros (a decode step
    still slides its window); ``padded_tail``, a chunk's tail is taken at
    the bucket's end. ``main`` sets it for the life of the process, the
    tests through ``monkeypatch.setattr(ssm, *carry_fault(how))``."""
    from neuronx_distributed_inference_tpu.modules import ssm
    if how == "zero_tail":
        fresh = ssm._real_and_fresh

        def forgetful(*a):
            valid, n_valid, keep = fresh(*a)
            return valid, n_valid, keep & (valid.shape[1] == 1)
        return "_real_and_fresh", forgetful
    if how == "padded_tail":
        tail = ssm._conv_tail
        return "_conv_tail", lambda x, n_valid, K1, tail_=None: tail(
            x, n_valid * 0 + x.shape[1], K1, tail_)
    raise ValueError(f"unknown fault {how!r}: zero_tail or padded_tail")


def _head(ref, hf):
    """What the head reads: ``N(x_L; embedding_norm)``."""
    return lambda w, x: ref.rms_norm(x, w["model.embedding_norm.weight"],
                                     hf["norm_eps"])


def long_walk(cfg, seed, tokens, rows=4, new_tokens=32, block=256,
              second=None):
    """See the module docstring: ``scripts/gate56.py``'s walk (the rows, the
    release and the re-used slot, the comparison on the device) on this
    architecture's reference. ``first_chunk`` is the one chunk a row that
    starts from zeros, ``later_chunks`` the ones that continue a tail."""
    return _gate56().long_walk(cfg, seed, tokens, rows=rows,
                               new_tokens=new_tokens, block=block,
                               second=second, head=_head)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="lfm2-8b-a1b")
    ap.add_argument("--seed", type=int, default=2147483761)
    ap.add_argument("--long", type=int, default=2304)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--second", type=int, default=0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--skip-gate", action="store_true")
    ap.add_argument("--break", dest="fault", default="")
    args = ap.parse_args(argv)
    import jax
    from harness import build
    backend = jax.devices()[0].platform
    if backend == "cpu":
        from neuronx_distributed_inference_tpu.compat import \
            force_cpu_devices
        force_cpu_devices(1)
    if args.fault:
        from neuronx_distributed_inference_tpu.modules import ssm
        setattr(ssm, *carry_fault(args.fault))
    cfg = build.load_json("configs", args.config + ".json")
    out = {"backend": backend, "seed": args.seed, "config": args.config,
           "fault": args.fault or None}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = f"gate61-{backend}-{args.seed}" + (f"-{args.fault}"
                                              if args.fault else "")
    path = os.path.join(ROOT, "chiprun_out", name + ".json")

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
        out["long"] = long_walk(cfg, args.seed, args.long, rows=args.rows,
                                new_tokens=args.new,
                                second=args.second or None)
        print(json.dumps(out["long"], indent=1), flush=True)
    save()
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
