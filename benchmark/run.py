#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and fixed rate are data files found by
name (``benchmark/README.md``). One process: the served application, the
front door on a localhost port and the load generator (a thread) live here,
because the chip belongs to one process. Set-up is everything from process
start (the interpreter's and jax's imports included) to "gate passed, every
graph of the cell warm", less the one call in which the TPU runtime starts
(``jax.devices()``, the first touch of the backend: 4-8 s that vary by
seconds with how the last process left the chip, printed as
``runtime_start_s``); then the load starts, runs ``lead_s`` before the window
opens and is still on when it closes.

``--trace 0`` prints the cell's end-to-end metrics, read at the client with
telemetry and the flight recorder off. ``--trace 1`` switches both on,
profiles a short slice of the window and prints the per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in a
traced run). With no TPU, or fewer chips than the cell asks for, it prints
one line on standard error and exits 2 with no result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OUT_DIR = os.path.join(ROOT, ".bench_out")       # traces; git-ignored
TRACE_SLICE_S = 3.0          # profiled part of the window, in a traced run
TRACE_OFFSET_S = 2.0         # ... starting this long after the window opens


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_PROCESS_START:7.1f}s] {msg}",
          flush=True)


# ---------------------------------------------------------------------------
# the cell, from its data files
# ---------------------------------------------------------------------------

def load_cell(name: str) -> Dict[str, Any]:
    """Everything that defines cell ``name``: its ``workloads`` entry (if it
    has one), its cell file, configuration, mix and metric lists."""
    from harness import build
    load_json = build.load_json
    with open(os.path.join(build.DATA_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    cell = load_json("cells", name + ".json")
    if entry is not None:
        for key in ("config", "traffic", "chips"):
            if cell[key] != entry[key]:
                raise ValueError(f"cell {name}: {key} is {cell[key]!r} in "
                                 f"its file and {entry[key]!r} in "
                                 "BENCHMARK.json")
    metrics_of = cell.get("metrics_as", name)

    def listed(group: str) -> List[Dict[str, Any]]:
        return [m for m in bench[group]
                if "workloads" not in m or metrics_of in m["workloads"]]
    return {"name": name, "cell": cell,
            "config": load_json("configs", cell["config"] + ".json"),
            "mix": load_json("traffic", cell["traffic"] + ".json"),
            "end_to_end": listed("end_to_end"),
            "per_layer": listed("per_layer")}


# ---------------------------------------------------------------------------
# snapshots for the per-layer readers
# ---------------------------------------------------------------------------

def snapshot(adapter, engine, driver) -> Dict[str, Any]:
    from neuronx_distributed_inference_tpu.telemetry import get_registry
    counters = {f"host_stats.{k}": v for k, v in adapter.host_stats.items()}
    counters.update({f"engine.{k}": v for k, v in engine.stats.items()})
    counters["client.tokens"] = sum(len(r.token_times)
                                    for r in list(driver.logs))
    from neuronx_distributed_inference_tpu.serving.warmup import memory_ledger
    kv = memory_ledger(adapter)["kv"]
    counters["kv.live_tokens"] = kv["live_tokens"]
    counters["kv.blocks_in_use"] = kv["blocks"]["in_use"]
    counters["kv.blocks_usable"] = kv["blocks"]["usable"]
    counters["kv.live_rows"] = len(adapter.running_ids)
    reg = get_registry()
    prom = reg.snapshot()["metrics"] if reg.enabled else {}
    return {"counters": counters, "prom": prom}


# ---------------------------------------------------------------------------
# the profiled slice
# ---------------------------------------------------------------------------

def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # device planes are what is read
    opts.host_tracer_level = 1
    return opts


def calibrate_programs(app, widths, trace_dir: str) -> Dict[str, Any]:
    """Run every warmed program once inside a profiler session of its own, in
    a known order, and read back which fingerprint is which width."""
    import jax
    from harness import reduce_trace
    from neuronx_distributed_inference_tpu.serving.warmup import precompile
    shutil.rmtree(trace_dir, ignore_errors=True)
    order = []
    jax.block_until_ready(app.cache)      # nothing of the warm-up in flight
    jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    try:
        for w in widths:
            rep = precompile(app, widths=[w])
            jax.block_until_ready(app.cache)
            order += [(g["kind"], g["bucket"]) for g in rep["graphs"]]
    finally:
        jax.profiler.stop_trace()
    planes = reduce_trace.load_xplane(reduce_trace.find_xplane(trace_dir))
    if not reduce_trace.device_planes(planes):
        say("calibration trace has no device plane: per-program metrics "
            "will be left out")
        return {}
    return reduce_trace.calibrate(planes, order)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

async def serve_window(spec, app, args, programs) -> Dict[str, Any]:
    """Stand the front door up, drive the load, and collect the client's
    event log with the window's edges (and, traced, the snapshots and the
    reduced trace)."""
    import jax
    from harness import loadgen, reduce_trace
    from harness.build import hf_config, serve_stack
    from harness.client import LoadDriver
    cfg, mix, cell = spec["config"], spec["mix"], spec["cell"]
    lead, grace = float(mix["lead_s"]), float(mix["grace_s"])
    requests = loadgen.make_requests(
        mix, rate=cell.get("rate_rps"), span_s=lead + args.seconds + grace)
    prompts = loadgen.prompt_tokens(requests, seed=args.seed,
                                    vocab=hf_config(cfg)["vocab_size"])
    adapter, engine, frontend = serve_stack(app, cfg)
    host, port = await frontend.start()
    clients = (mix.get("clients_per_batch_row", 0)
               * cfg["serve"]["batch_size"])
    driver = LoadDriver(host, port, requests, prompts,
                        loop_kind=mix["loop"], clients=clients)
    out: Dict[str, Any] = {"driver": driver, "engine": engine,
                           "adapter": adapter}
    loop = asyncio.get_running_loop()
    t_start = time.perf_counter() + 0.05
    lo, hi = t_start + lead, t_start + lead + args.seconds
    out["lo"], out["hi"] = lo, hi

    async def until(t: float) -> None:
        delay = t - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)

    try:
        driver.start(t_start)
        await until(lo)
        out["before"] = snapshot(adapter, engine, driver)
        if args.trace:
            trace_dir = os.path.join(OUT_DIR, f"trace-{spec['name']}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            await until(lo + min(TRACE_OFFSET_S, args.seconds / 4))
            out["slice_before"] = snapshot(adapter, engine, driver)
            await loop.run_in_executor(
                None, lambda: jax.profiler.start_trace(
                    trace_dir, profiler_options=_profile_options()))
            await asyncio.sleep(min(TRACE_SLICE_S, args.seconds / 3))
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            out["slice_after"] = snapshot(adapter, engine, driver)
            planes = reduce_trace.load_xplane(
                reduce_trace.find_xplane(trace_dir))
            out["trace"] = reduce_trace.reduce_trace(planes, programs)
        await until(hi)
        out["after"] = snapshot(adapter, engine, driver)
        t_give_up = hi + (grace if mix["loop"] == "open" else 0.0)
        while (driver.waiting_for_first_token(lo, hi)
               and time.perf_counter() < t_give_up):
            await asyncio.sleep(0.05)
    finally:
        try:
            driver.stop()
        finally:
            await frontend.stop()
    out["warm"] = app.warmup_state()
    return out


def run_cell(args, require_chips: Optional[Callable[[int], Dict]] = None
             ) -> Dict[str, Any]:
    """The whole run; returns the last line's object. ``require_chips`` is
    the device check (tests pass their own)."""
    from harness import build, metrics, readers
    spec = load_cell(args.workload)
    cfg, mix = spec["config"], spec["mix"]
    import jax                    # its import is set-up; the call below is not
    t_device = time.perf_counter()
    device = (require_chips or build.require_chips)(spec["cell"]["chips"])
    # the TPU runtime's own start: this one call (jax.devices(), the first
    # touch of the backend) depends on how the last process left the chip,
    # not on this code
    runtime_start_s = time.perf_counter() - t_device
    say(f"cell {spec['name']}: config {spec['cell']['config']}, traffic "
        f"{spec['cell']['traffic']}, seed {args.seed}, {args.seconds}s, "
        f"trace {args.trace}")
    say(f"device: platform {device['platform']}, device_kind "
        f"{device['kind']!r}, count {device['count']} (runtime_start_s "
        f"{runtime_start_s:.2f}: jax.devices() alone, left out of setup_s)")
    peaks = build.peaks_for(device["kind"])

    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.serving.warmup import precompile
    from neuronx_distributed_inference_tpu.telemetry.trace import \
        enable_recorder
    from neuronx_distributed_inference_tpu.utils.compile_cache import \
        configure_compile_cache
    say(f"compile cache: {configure_compile_cache()}")

    # -- set-up: gate, weights, pool, warm-up ------------------------------
    gate = build.logit_gate(cfg, args.seed)
    say(f"logit gate: {gate}")
    app = build.build_app(cfg)
    app.init_random_weights(seed=args.seed).init_cache()
    jax.block_until_ready((app.params, app.cache))
    say("weights and pool on the device")
    widths = build.warm_widths(cfg, mix)
    report = precompile(app, widths=widths)
    say(f"precompile widths {widths}: {report['n_graphs']} graphs, "
        f"{report['n_compiles']} XLA builds, {report['n_cache_loads']} cache "
        f"loads, {report['n_warm_hits']} warm, "
        f"{report['total_seconds']:.1f}s; kernels {report['kernels']}")
    programs = None
    if args.trace:
        telemetry.enable()
        enable_recorder()
        programs = calibrate_programs(
            app, widths, os.path.join(OUT_DIR, f"calib-{spec['name']}"))
        say(f"programs in the trace: {programs}")
    setup_s = time.perf_counter() - T_PROCESS_START - runtime_start_s
    say(f"set-up done: setup_s {setup_s:.2f} + runtime_start_s "
        f"{runtime_start_s:.2f} = {setup_s + runtime_start_s:.2f} from "
        "process start")

    # -- the window --------------------------------------------------------
    got = asyncio.run(serve_window(spec, app, args, programs))
    lo, hi = got["lo"], got["hi"]
    vocab = build.hf_config(cfg)["vocab_size"]
    e2e = metrics.end_to_end(got["driver"].logs, lo, hi, vocab)
    e2e["setup_s"] = setup_s
    say(f"window: {e2e['attempted']} requests started, "
        f"{e2e['completed_in_window']} completed "
        f"({e2e['requests_per_s']:.3f} req/s), {e2e['failed']} failed, "
        f"{e2e['tokens_in_window']} tokens; samples {e2e['samples']}")
    for key in ("ttft_p50_ms", "ttft_p90_ms", "itl_p50_ms", "itl_p95_ms",
                "tokens_per_s", "generator_lateness_p95_ms"):
        if key in e2e:
            say(f"  {key} = {e2e[key]:.3f}")
    for edge in ("before", "after"):
        c = got[edge]["counters"]
        say(f"  kv pool at the window's {edge} edge: {c['kv.live_rows']} "
            f"rows, {c['kv.live_tokens']} live tokens, "
            f"{c['kv.blocks_in_use']} of {c['kv.blocks_usable']} blocks "
            "booked (cached prefixes included)")
    for index, why in list(e2e["faults"].items())[:5]:
        say(f"  request {index} failed: {why}")

    # -- correctness -------------------------------------------------------
    warm, stats = got["warm"], got["engine"].stats
    counters_ok = (warm["steady_state"] and not warm["incidents"]
                   and not stats["preempt_requeues"]
                   and not stats["priority_preemptions"]
                   and not stats["step_retries"]
                   and not got["adapter"].preempted)
    streams_ok = not any(r.ended is not None and r.index in e2e["faults"]
                         for r in got["driver"].logs)
    say(f"gate: logits {gate['passed']}, streams {streams_ok}, counters "
        f"{counters_ok} (incidents {warm['incidents']}, engine stats "
        f"{stats})")
    say(f"host_stats: {dict(got['adapter'].host_stats)}")

    # -- the line ----------------------------------------------------------
    device_out = dict(device, memory_peak_bytes=build.memory_peak_bytes(
        cfg["tp"]))
    result: Dict[str, Any] = {
        "correct": bool(gate["passed"] and streams_ok and counters_ok),
        "attempted": e2e["attempted"], "failed": e2e["failed"],
        "metrics": {}, "device": device_out}
    if not args.trace:
        for m in spec["end_to_end"]:
            if m["name"] not in e2e:
                raise RuntimeError(f"the run produced no sample for "
                                   f"{m['name']}")
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
        return result
    trace = got.get("trace")
    ctx = {"before": got["before"], "after": got["after"], "trace": trace,
           "e2e": e2e,
           "report": report, "config": cfg, "cell": spec["cell"],
           "peaks": peaks, "warm_widths": widths,
           "slice": {"before": got.get("slice_before"),
                     "after": got.get("slice_after")}}
    for m in spec["per_layer"]:
        value = readers.read_metric(m["name"], ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if trace and trace.get("window_s"):
        device_out["busy_s"] = trace["busy_s"]
        device_out["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        say(f"programs in the slice: {json.dumps(trace['programs'])}")
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, f"reduced-{spec['name']}.json"),
                  "w") as f:
            json.dump(trace, f, indent=1)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import neuronx_distributed_inference_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the system under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return 2
    from harness.build import NoChip
    try:
        result = run_cell(args)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
