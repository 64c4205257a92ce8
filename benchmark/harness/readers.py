"""Per-layer metrics: one small data file each, read by a reader kind.

``layer_metrics/<name>.json`` holds ``layer``, ``unit``, ``better``,
``source``, ``moves`` and ``reader``: a kind from the table below with its
``args``. (The cells that report it are listed in one place, the
``workloads`` of its ``per_layer`` entry in ``BENCHMARK.json``.) A metric
that needs new code is a file ``layer_metrics/<name>.py`` with
``read(ctx) -> float | None`` beside its ``.json`` (whose reader kind is then
``"python"``), found by name.

A reader that finds nothing to read returns None and the harness leaves the
metric out of the line.

``ctx`` (a dict) is what a traced run collected:

* ``before`` / ``after``: snapshots at the window's edges, each
  ``{"counters": {flat name: number}, "prom": <registry.snapshot()["metrics"]>}``.
  Flat counter names are ``host_stats.<key>`` (the adapter's exact host
  counts), ``engine.<key>`` (``ServingEngine.stats``) and ``client.<key>``
  (``tokens``: token events the client has received).
* ``trace``: the output of ``reduce_trace.reduce_trace`` on the profiled
  slice, or None; ``report``: the ``precompile()`` report;
* ``e2e``: what ``metrics.end_to_end`` computed from the client's log of this
  (traced) run — the same quantities as the untraced run's, slowed by the
  instrumentation;
* ``config``, ``cell``, ``peaks``, ``warm_widths``, ``slice``: the
  configuration file, the cell file, the chip's row of ``peaks.json``, the
  step widths the cell warmed, and the counters/occupancy sampled over the
  profiled slice.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional

from . import host_spans
from .build import BENCH_DIR, load_json, load_module


def _counter(snap: Dict[str, Any], name: str) -> float:
    """A flat counter, or ``prom:<metric>{k=v,...}``: the sum of a Prometheus
    counter's series whose labels match."""
    if not name.startswith("prom:"):
        return float(snap["counters"].get(name, 0.0))
    metric, _, rest = name[5:].partition("{")
    want = dict(kv.split("=") for kv in rest.rstrip("}").split(",") if kv)
    series = snap["prom"].get(metric, {}).get("series", [])
    return float(sum(s["value"] for s in series
                     if all(s["labels"].get(k) == v
                            for k, v in want.items())))


def _delta(ctx, names: List[str]) -> float:
    return sum(_counter(ctx["after"], n) - _counter(ctx["before"], n)
               for n in names)


def counter_ratio(ctx, num: List[str], den: List[str],
                  scale: float = 1.0) -> Optional[float]:
    """Window delta of ``sum(num)`` over window delta of ``sum(den)``."""
    n, d = _delta(ctx, num), _delta(ctx, den)
    return None if d <= 0 else scale * n / d


def prom_quantile(ctx, metric: str, quantile: float, scale: float = 1.0,
                  labels: Optional[Dict[str, str]] = None) -> Optional[float]:
    """A quantile of a Prometheus histogram over the window (bucket counts
    after minus before, summed over matching series), interpolated linearly
    inside its bucket as ``histogram_quantile`` does."""
    labels = labels or {}

    def buckets(snap):
        acc: Dict[float, float] = {}
        for s in snap["prom"].get(metric, {}).get("series", []):
            if all(s["labels"].get(k) == v for k, v in labels.items()):
                for le, c in s["buckets"]:
                    acc[le] = acc.get(le, 0.0) + c
        return acc
    a, b = buckets(ctx["after"]), buckets(ctx["before"])
    cum = sorted((le, a[le] - b.get(le, 0.0)) for le in a)
    if not cum or cum[-1][1] <= 0:
        return None
    target = quantile * cum[-1][1]
    lo_le, lo_c = 0.0, 0.0
    for le, c in cum:
        if c >= target:
            if c == lo_c:
                return scale * le
            return scale * (lo_le + (le - lo_le) * (target - lo_c)
                            / (c - lo_c))
        lo_le, lo_c = le, c
    return scale * cum[-1][0]


def _program_label(ctx, kind: str, width: Any) -> str:
    if width == "widest":
        width = max(ctx["warm_widths"])
    return f"{kind}.w{width}"


def trace_program_median(ctx, kind: str, width: Any) -> Optional[float]:
    """Median device duration, in ms, of the executions of one warmed step
    program in the profiled slice (``width``: a number or ``"widest"``)."""
    tr = ctx.get("trace")
    if not tr or not tr.get("programs"):
        return None
    prog = tr["programs"].get(_program_label(ctx, kind, width))
    return None if prog is None else prog["median_ms"]


def trace_idle_share(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or tr.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def client_field(ctx, field: str) -> Optional[float]:
    """A quantity of the client's own log in the traced run (``ctx["e2e"]``)."""
    value = ctx.get("e2e", {}).get(field)
    return None if value is None else float(value)


def report_field(ctx, field: str) -> Optional[float]:
    value = ctx["report"].get(field)
    return None if value is None else float(value)


KINDS: Dict[str, Callable[..., Optional[float]]] = {
    "counter_ratio": counter_ratio,
    "prom_quantile": prom_quantile,
    "trace_program_median": trace_program_median,
    # args kind, width, scope; a scope named in a metric's file is a scope
    # the trace is split by (host_spans.known_scopes)
    "trace_scope_ms": host_spans.program_scope_ms,
    "trace_idle_share": trace_idle_share,
    "report_field": report_field,
    "client_field": client_field,
}


def read_metric(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    """The value of per-layer metric ``name`` from its file(s), or None."""
    spec = load_json("layer_metrics", name + ".json")
    reader = spec["reader"]
    if reader["kind"] == "python":
        path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
        return load_module(path).read(ctx)
    if reader["kind"] not in KINDS:
        raise KeyError(f"layer metric {name}: unknown reader kind "
                       f"{reader['kind']!r}; known: {sorted(KINDS)}")
    return KINDS[reader["kind"]](ctx, **reader.get("args", {}))
