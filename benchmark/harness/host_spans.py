"""What the one-timeline per-layer metrics share (ISSUE 25).

The program's flight recorder puts its spans — ``pass.expire``,
``pass.preempt``, ``pass.admit``, ``pass.dispatch``, ``loop.yield``,
``loop.idle`` at the top of the serving loop's thread, ``dispatch.prefill_chunk``,
``run.<kind>`` and ``fetch.tokens`` inside them — in two places a reader can
reach:

* the counter ``nxdi_host_seconds_total{span, under}`` (seconds per span and
  per the span open around it), read over the whole window from the two
  snapshots ``run.py`` takes: :func:`host_seconds` and the arithmetic below;
* the ``/host:CPU`` plane of the profiled slice's ``.xplane.pb``, as TraceMe
  events on the same clock as the device planes: :func:`load_slice`,
  :func:`idle_by_span`.

The device side gets names too: every ``XLA Ops`` event carries the HLO
``op_name`` of its instruction, whose path holds the ``jax.named_scope`` the
model opened (``attn``, ``moe``, ...): :func:`scope_seconds`.

The interval arithmetic and both reductions are pure functions over the
``{plane: {line: [Event]}}`` structure of ``reduce_trace``, checked on
hand-built lists in ``tests/test_host_spans.py``. A program without the
spans, the counter or the scopes (the parent of the PR that added them)
gives every reader nothing to read: each returns None and raises nothing.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from . import reduce_trace, xplane_wire
from .build import ROOT
from .reduce_trace import Event, Planes

HOST_SECONDS = "nxdi_host_seconds_total"
HOST_PLANE = "/host:CPU"

SCHED = ("pass.expire", "pass.preempt", "pass.admit")
DISPATCH = ("pass.dispatch", "dispatch.prefill_chunk")
YIELD = ("loop.yield",)
NOWORK = ("loop.idle",)
FETCH = ("fetch.tokens",)
TOP = SCHED + ("pass.dispatch",) + YIELD + NOWORK
SPANS = frozenset(TOP + DISPATCH + FETCH)

#: the scopes ``models/model_base.py`` opens today, outermost wins; a scope
#: the program opens for a new kind of layer joins them by being named in a
#: metric's file (:func:`known_scopes`)
SCOPES = ("embed", "attn", "moe", "mlp", "lm_head", "sample")
#: stats of an ``XLA Ops`` event's METADATA that may carry the HLO
#: ``op_name`` (the scope path), in the order tried; ``tf_op`` is what a v5e
#: trace has (looked at by hand in PR 25, PERF.md §3)
SCOPE_STATS = ("tf_op", "op_name")

Intervals = List[Tuple[float, float]]


# ---------------------------------------------------------------------------
# host seconds over the window, from the registry snapshots
# ---------------------------------------------------------------------------

def host_seconds(ctx: Dict[str, Any]) -> Dict[Tuple[str, str], float]:
    """Window delta of ``nxdi_host_seconds_total`` by ``(span, under)``;
    empty when the program has no such counter."""
    def series(snap):
        rows = snap.get("prom", {}).get(HOST_SECONDS, {}).get("series", [])
        return {(s["labels"].get("span", ""), s["labels"].get("under", "")):
                float(s["value"]) for s in rows}
    after, before = series(ctx["after"]), series(ctx["before"])
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def own_seconds(delta, spans: Iterable[str]) -> float:
    spans = set(spans)
    return sum(v for (span, _), v in delta.items() if span in spans)


def self_seconds(delta, spans: Iterable[str]) -> float:
    """Seconds inside ``spans`` that no span recorded under them explains
    (on-chip-measurement guide §4: self time)."""
    spans = set(spans)
    return own_seconds(delta, spans) - sum(
        v for (_, under), v in delta.items() if under in spans)


def run_seconds(delta) -> float:
    return sum(v for (span, _), v in delta.items() if span.startswith("run."))


def dispatches(ctx: Dict[str, Any]) -> float:
    def total(snap):
        c = snap["counters"]
        return (c.get("host_stats.dispatches", 0)
                + c.get("host_stats.prefill_dispatches", 0))
    return float(total(ctx["after"]) - total(ctx["before"]))


def window_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """Length of the measured window, from the client's own arithmetic
    (``tokens_per_s`` is tokens in the window over its seconds)."""
    e2e = ctx.get("e2e", {})
    if not e2e.get("tokens_in_window") or not e2e.get("tokens_per_s"):
        return None
    return e2e["tokens_in_window"] / e2e["tokens_per_s"]


def host_breakdown(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The loop thread's seconds over the window, by who spent them. The
    six parts add up to the top-level spans' total (``named_s``);
    ``coverage`` is that total over the window's length."""
    delta = host_seconds(ctx)
    n = dispatches(ctx)
    if not delta or n <= 0:
        return None
    out = {"sched_s": self_seconds(delta, SCHED),
           "run_prep_s": run_seconds(delta),
           "dispatch_self_s": self_seconds(delta, DISPATCH),
           "loop_yield_s": self_seconds(delta, YIELD),
           "fetch_wait_s": own_seconds(delta, FETCH),
           "loop_idle_s": self_seconds(delta, NOWORK)}
    out["named_s"] = own_seconds(
        {k: v for k, v in delta.items() if k[1] == ""}, TOP)
    out["dispatches"] = n
    window = window_seconds(ctx)
    if window:
        out["window_s"] = window
        out["coverage"] = out["named_s"] / window
    return out


def per_dispatch_ms(ctx: Dict[str, Any], part: str) -> Optional[float]:
    b = host_breakdown(ctx)
    return None if b is None else 1e3 * b[part] / b["dispatches"]


# ---------------------------------------------------------------------------
# the profiled slice's xplane
# ---------------------------------------------------------------------------

def slice_trace_dir(ctx: Dict[str, Any],
                    out_dir: Optional[str] = None) -> Optional[str]:
    """Where ``run.py`` wrote this run's slice: ``trace-<cell>`` under its
    output directory. ``ctx`` carries the cell's file, not its name, so the
    name is the cell file that reads the same; failing that, the newest
    ``trace-*`` directory (never a ``calib-*`` one)."""
    if out_dir is None:              # the entry point's own OUT_DIR
        entry = sys.modules.get("run") or sys.modules.get("__main__")
        out_dir = getattr(entry, "OUT_DIR", os.path.join(ROOT, ".bench_out"))
    from . import build
    for base in build.data_dirs():
        for path in sorted(glob.glob(os.path.join(base, "cells", "*.json"))):
            with open(path) as f:
                same = json.load(f) == ctx.get("cell")
            name = os.path.splitext(os.path.basename(path))[0]
            cand = os.path.join(out_dir, f"trace-{name}")
            if same and os.path.isdir(cand):
                return cand
    dirs = [d for d in glob.glob(os.path.join(out_dir, "trace-*"))
            if os.path.isdir(d)]
    return max(dirs, key=os.path.getmtime) if dirs else None


def load_planes(path: str) -> Planes:
    """The lines the readers need, with the stats they need: per chip the
    ``XLA Modules`` line and the ``XLA Ops`` line (``scope``: the HLO
    op_name path, a stat of the event's metadata, so read with
    ``xplane_wire``); of the host plane the recorder's span events
    (``pass_id``), whatever thread line they are on."""
    planes = xplane_wire.read_planes(
        path,
        want_plane=lambda p: (p == HOST_PLANE
                              or p.startswith(reduce_trace.DEVICE_PREFIX)),
        want_line=lambda p, ln: (p == HOST_PLANE or ln in (
            reduce_trace.MODULES_LINE, reduce_trace.OPS_LINE)),
        keep_stats=SCOPE_STATS + ("pass_id",),
        short_name=lambda ln, name: (reduce_trace.op_name(name)
                                     if ln == reduce_trace.OPS_LINE
                                     else name))
    for plane, lines in planes.items():
        if plane == HOST_PLANE:
            for ln in list(lines):
                lines[ln] = [e for e in lines[ln] if e.name in SPANS
                             or e.name.startswith("run.")]
                if not lines[ln]:
                    del lines[ln]
            continue
        for e in lines.get(reduce_trace.OPS_LINE, ()):
            e.stats = {"scope": next(
                (str(e.stats[k]) for k in SCOPE_STATS
                 if "/" in str(e.stats.get(k, ""))), "")}
    return planes


_CACHE: Dict[Tuple[str, float], Dict[str, Any]] = {}


def load_slice(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"planes", "idle", "scopes"}`` of this run's profiled slice, read
    once per file; None where there is no slice (an untraced or CPU run)."""
    trace_dir = slice_trace_dir(ctx)
    if trace_dir is None:
        return None
    try:
        path = reduce_trace.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        planes = load_planes(path)
        _CACHE[key] = {"planes": planes, "idle": idle_by_span(planes),
                       "scopes": scope_seconds(planes, known_scopes())}
    return _CACHE[key]


# ---------------------------------------------------------------------------
# interval arithmetic (pure)
# ---------------------------------------------------------------------------

def merge(intervals: Iterable[Tuple[float, float]]) -> Intervals:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: Intervals = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def complement(busy: Intervals, lo: float, hi: float) -> Intervals:
    """``[lo, hi]`` less the merged intervals ``busy``."""
    out, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            out.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """Merged ``a`` less merged ``b``."""
    if not a:
        return []
    return intersect(a, complement(b, a[0][0], a[-1][1]))


def seconds(intervals: Intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


# ---------------------------------------------------------------------------
# device idle time by what the host was doing (pure)
# ---------------------------------------------------------------------------

def host_span_events(planes: Planes) -> List[Event]:
    return [e for line in planes.get(HOST_PLANE, {}).values() for e in line]


def idle_by_span(planes: Planes) -> Optional[Dict[str, float]]:
    """The slice's device idle time, split by the host span it fell in.

    The window and the idle intervals are ``reduce_trace``'s: first to last
    device operation over all chips, and per chip the complement of the
    union of ``XLA Ops`` in it. Host classes, each a merged interval list
    from the host plane of the same file (one clock):

    * ``dispatch``: ``pass.dispatch`` and every ``dispatch.prefill_chunk``
      (the default adapter runs those inside ``pass.admit``): the adapter
      and the step graphs' host side at work;
    * ``sched``: ``pass.expire``, ``pass.preempt``, ``pass.admit`` less the
      above: the scheduler's own work;
    * ``yield``: ``loop.yield``; ``nowork``: ``loop.idle``.

    Seconds are the mean over chips; ``remainder_s`` is idle time under no
    span (between spans, or outside the recorder's reach), so the five add
    up to ``idle_s`` = ``window_s`` - busy. None without a device plane or
    without span events (a program that has none)."""
    dev = reduce_trace.device_planes(planes)
    spans = host_span_events(planes)
    if not dev or not any(e.name in TOP for e in spans):
        return None
    ops = reduce_trace.OPS_LINE
    lo = min(e.start for p in dev for e in planes[p][ops])
    hi = max(e.end for p in dev for e in planes[p][ops])

    def of(names):
        return merge((e.start, e.end) for e in spans if e.name in names)
    dispatch = of(DISPATCH)
    classes = {"dispatch": dispatch, "sched": subtract(of(SCHED), dispatch),
               "yield": of(YIELD), "nowork": of(NOWORK)}
    out = {f"{k}_s": 0.0 for k in classes}
    idle_total = 0.0
    for p in dev:
        idle = complement(merge((e.start, e.end) for e in planes[p][ops]),
                          lo, hi)
        idle_total += seconds(idle) / len(dev)
        for k, iv in classes.items():
            out[f"{k}_s"] += seconds(intersect(idle, iv)) / len(dev)
    out["window_s"] = hi - lo
    out["idle_s"] = idle_total
    out["remainder_s"] = idle_total - sum(out[f"{k}_s"] for k in classes)
    return out


def idle_share(ctx: Dict[str, Any], host_class: str) -> Optional[float]:
    """Per cent of the slice in which the device idled while the host was in
    ``host_class``; the first reader of a run prints the whole split."""
    got = load_slice(ctx)
    idle = got and got["idle"]
    if not idle or idle["window_s"] <= 0:
        return None
    if not got.get("said"):
        got["said"] = True
        pct = {k: 100.0 * v / idle["window_s"] for k, v in idle.items()
               if k != "window_s"}
        print("[host_spans] device idle by host span, % of the slice: "
              + ", ".join(f"{k[:-2]} {v:.3f}" for k, v in pct.items()),
              flush=True)
    return 100.0 * idle[f"{host_class}_s"] / idle["window_s"]


# ---------------------------------------------------------------------------
# device self time by scope (pure)
# ---------------------------------------------------------------------------

def known_scopes() -> Tuple[str, ...]:
    """The scope names the trace is split by: :data:`SCOPES` and every
    ``scope`` that a ``layer_metrics/*.json`` gives its reader as an argument
    (``trace_scope_ms``), so a metric of a new scope is a new file."""
    from . import build
    found = set()
    for base in build.data_dirs():
        for path in glob.glob(os.path.join(base, "layer_metrics", "*.json")):
            with open(path) as f:
                args = json.load(f).get("reader", {}).get("args", {})
            if isinstance(args.get("scope"), str):
                found.add(args["scope"])
    return SCOPES + tuple(sorted(found - set(SCOPES)))


def scope_of(path: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The outermost of ``scopes`` on an HLO op_name path
    (``jit(paged_forward_step)/while/body/closed_call/attn/dot_general:``)."""
    for part in path.rstrip(":").split("/"):
        if part in scopes:
            return part
    return None


def scope_seconds(planes: Planes, scopes: Sequence[str] = SCOPES
                  ) -> Dict[str, Dict[str, Any]]:
    """Per compiled program of the first chip (``reduce_trace.program_key``):
    its executions (``count``, ``total_s``: the same two numbers
    ``reduce_trace`` reports under the program's label) and the self time of
    its device operations by scope (``scopes``; operations under no scope
    are ``""``). Control operations count their self time only.

    The compiler leaves some operations without their path — on a v5e the
    ``ragged-dot`` custom calls of the expert matmuls read ``ragged-dot-none``
    (PR 25, PERF.md §3). An operation under no scope takes the scope of its
    neighbours when the nearest scoped operations before and after it in the
    same execution agree; between two different scopes (the layer loop's
    weight slices, the residual adds) it stays under none."""
    dev = reduce_trace.device_planes(planes)
    if not dev:
        return {}
    first = planes[dev[0]]
    mods = sorted(first.get(reduce_trace.MODULES_LINE, ()),
                  key=lambda e: e.start)
    out: Dict[str, Dict[str, Any]] = {}
    per_exec: List[List[Tuple[Optional[str], float]]] = [[] for _ in mods]
    for m in mods:
        slot = out.setdefault(reduce_trace.program_key(m),
                              {"count": 0, "total_s": 0.0, "scopes": {}})
        slot["count"] += 1
        slot["total_s"] += m.dur
    mi = 0
    for e, own in reduce_trace.self_times(first[reduce_trace.OPS_LINE]):
        while mi < len(mods) and mods[mi].end < e.start:
            mi += 1
        if mi < len(mods) and mods[mi].start <= e.start <= mods[mi].end:
            per_exec[mi].append((scope_of(e.stats.get("scope", ""), scopes),
                                 own))
    for m, ops in zip(mods, per_exec):
        after: List[Optional[str]] = [None] * len(ops)
        nxt = None
        for i in range(len(ops) - 1, -1, -1):
            after[i] = nxt
            nxt = ops[i][0] or nxt
        scopes = out[reduce_trace.program_key(m)]["scopes"]
        before = None
        for (scope, own), nxt in zip(ops, after):
            if scope is None:
                scope = before if before is not None and before == nxt else ""
            else:
                before = scope
            scopes[scope] = scopes.get(scope, 0.0) + own
    return out


def program_scope_ms(ctx: Dict[str, Any], kind: str, width: Any,
                     scope: str) -> Optional[float]:
    """Self time of the device operations under ``scope`` per execution of
    one warmed step program, in ms. The program is found by its label in the
    reduced trace (``<kind>.w<width>``; ``width`` a number or ``"widest"``):
    the one program of the slice with the label's execution count and total
    duration. None where the program did not run, carries no scope, or
    cannot be told apart."""
    tr = ctx.get("trace") or {}
    if width == "widest":
        width = max(ctx["warm_widths"])
    want = tr.get("programs", {}).get(f"{kind}.w{width}")
    got = load_slice(ctx)
    if not want or not got:
        return None
    same = [p for p in got["scopes"].values()
            if p["count"] == want["count"]
            and abs(p["total_s"] - want["total_s"])
            <= 1e-9 + 1e-6 * want["total_s"]]
    if len(same) != 1 or not any(v for s, v in same[0]["scopes"].items()
                                 if s):
        return None
    return 1e3 * same[0]["scopes"].get(scope, 0.0) / same[0]["count"]


def say_host_breakdown(ctx: Dict[str, Any]) -> None:
    b = host_breakdown(ctx)
    if b is not None:
        print("[host_spans] loop thread over the window: "
              + ", ".join(f"{k} {v:.4f}" for k, v in b.items()), flush=True)

