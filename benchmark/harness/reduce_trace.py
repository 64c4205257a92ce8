"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction works on a plain structure — ``{plane: {line: [Event]}}`` —
so it can be checked on a hand-built list (``tests/test_reduce_trace.py``);
:func:`load_xplane` fills that structure from a file with
``jax.profiler.ProfileData`` and nothing else.

What a v5e trace looks like (looked at by hand in PR 24, PERF.md §5): one
plane per chip named ``/device:TPU:<n>``; on it the line ``XLA Modules`` has
one event per execution of a compiled program, named
``<jit name>(<fingerprint>)``, and the line ``XLA Ops`` has one event per
device operation, named as the compiled HLO names it. Every other plane is
the host's. Programs of one jit name and different shapes differ only in the
fingerprint, so the harness learns which fingerprint is which width from a
calibration trace in set-up (``calibrate``).

Run as a script to look at a trace by hand::

    python benchmark/harness/reduce_trace.py <file.xplane.pb>
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclass
class Event:
    name: str
    start: float                      # seconds on the trace's clock
    dur: float                        # seconds
    stats: Dict[str, Any] = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


Planes = Dict[str, Dict[str, List[Event]]]


def op_name(hlo: str) -> str:
    """``paged_decode_attention.5`` from the full instruction text the ops
    line prints (``%paged_decode_attention.5 = bf16[...] custom-call(...)``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str, device_only: bool = True) -> Planes:
    from jax.profiler import ProfileData
    out: Planes = {}
    for plane in ProfileData.from_file(path).planes:
        if device_only and not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            short = line.name in (OPS_LINE, "Async XLA Ops")
            for e in line.events:
                evs.append(Event(op_name(e.name) if short else e.name,
                                 e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9,
                                 {k: v for k, v in e.stats
                                  if k == "program_id"}))
    return out


def device_planes(planes: Planes) -> List[str]:
    def chip(name: str) -> int:
        tail = name[len(DEVICE_PREFIX):]
        return int(tail) if tail.isdigit() else -1
    return sorted((p for p in planes if p.startswith(DEVICE_PREFIX)
                   and chip(p) >= 0 and OPS_LINE in planes[p]), key=chip)


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """``(event, self seconds)`` in start order. On the ops line a control
    operation (the ``while`` of the layer loop, a conditional) spans the
    operations of its body; its self time is its duration minus theirs, so
    that totals over operations add up to busy time."""
    order = sorted(events, key=lambda e: (e.start, -e.dur))
    self_s = [e.dur for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= order[stack[-1]].end + 1e-12:
            self_s[stack[-1]] -= e.dur
        stack.append(i)
    return [(e, max(t, 0.0)) for e, t in zip(order, self_s)]


def program_key(ev: Event) -> str:
    """What tells one compiled program from another in the modules line: the
    event's name (jit name + fingerprint), and the program id where the
    trace carries one."""
    pid = ev.stats.get("program_id")
    return ev.name if pid is None else f"{ev.name}#{pid}"


def calibrate(planes: Planes, order: Sequence[Tuple[str, int]]
              ) -> Dict[str, Tuple[str, int]]:
    """``{program key: (kind, width)}`` from a calibration trace in which the
    warmed step programs ran ONCE each, in the known ``order``.

    The trace also holds the small helper programs every dispatch runs first
    (the RNG split and its unstack): they run once per dispatch, so any
    program seen more than once here is a helper and is dropped — no program
    is told apart by its name (the step programs are all ``jit__unknown``
    until the tracing issue names them). Raises if what is left is not
    ``len(order)`` executions."""
    dev = device_planes(planes)
    if not dev:
        raise ValueError("calibration trace has no device plane")
    mods = sorted(planes[dev[0]].get(MODULES_LINE, ()), key=lambda e: e.start)
    seen: Dict[str, int] = {}
    for m in mods:
        seen[program_key(m)] = seen.get(program_key(m), 0) + 1
    once = [m for m in mods if seen[program_key(m)] == 1]
    if len(once) != len(order):
        raise ValueError(
            f"calibration ran {len(order)} step programs, the trace shows "
            f"{len(once)} programs that ran once: "
            f"{[(m.name, round(m.dur * 1e3, 3)) for m in mods]}")
    return {program_key(ev): tuple(what) for ev, what in zip(once, order)}


def reduce_trace(planes: Planes,
                 programs: Optional[Dict[str, Tuple[str, int]]] = None,
                 top: int = 10) -> Dict[str, Any]:
    """Busy and idle time, per-program durations, top operations and the
    longest idle gaps. ``programs`` (from :func:`calibrate`) gives programs
    readable labels ``<kind>.w<width>``; without it the key is the label.

    The window is the span from the first to the last device operation over
    all chips (the profiler's own session is a little longer at both ends;
    that part has no device event to measure against). Busy time is, per
    chip, the union of the intervals of ``XLA Ops``; ``busy_s`` is its mean
    over the chips. Programs, operations and gaps are read on the first
    chip."""
    dev = device_planes(planes)
    if not dev:
        return {"devices": [], "window_s": 0.0, "busy_s": 0.0}
    lo = min(e.start for p in dev for e in planes[p][OPS_LINE])
    hi = max(e.end for p in dev for e in planes[p][OPS_LINE])
    per_dev = {p: union_seconds((e.start, e.end)
                                for e in planes[p][OPS_LINE]) for p in dev}
    first = planes[dev[0]]
    label_of = {k: f"{kind}.w{w}" for k, (kind, w) in (programs or {}).items()}
    mods = sorted(first.get(MODULES_LINE, ()), key=lambda e: e.start)
    durations: Dict[str, List[float]] = {}
    for m in mods:
        key = program_key(m)
        durations.setdefault(label_of.get(key, key), []).append(m.dur)
    ops = self_times(first[OPS_LINE])
    # operations by the program execution that contains them
    by_prog: Dict[str, Dict[str, List[float]]] = {}
    totals: Dict[str, float] = {}
    mi = 0
    for e, own in ops:
        totals[e.name] = totals.get(e.name, 0.0) + own
        while mi < len(mods) and mods[mi].end < e.start:
            mi += 1
        if mi < len(mods) and mods[mi].start <= e.start <= mods[mi].end:
            key = program_key(mods[mi])
            slot = by_prog.setdefault(label_of.get(key, key), {})
            acc = slot.setdefault(e.name, [0.0, 0])
            acc[0] += own
            acc[1] += 1
    # idle gaps, named by the program that ends the gap
    gaps: Dict[str, float] = {}
    cursor, mi = lo, 0
    for e, _ in ops:
        if e.start > cursor:
            while mi < len(mods) and mods[mi].end < e.start:
                mi += 1
            nxt = (program_key(mods[mi]) if mi < len(mods)
                   and mods[mi].start <= e.start else "outside a program")
            name = "before " + label_of.get(nxt, nxt) \
                if nxt != "outside a program" else nxt
            gaps[name] = gaps.get(name, 0.0) + (e.start - cursor)
        cursor = max(cursor, e.end)
    return {
        "devices": dev,
        "window_s": hi - lo,
        "busy_s": sum(per_dev.values()) / len(per_dev),
        "busy_s_per_device": per_dev,
        "programs": {k: {"count": len(v),
                         "median_ms": 1e3 * statistics.median(v),
                         "total_s": sum(v)} for k, v in durations.items()},
        "ops_by_program": {k: {n: {"seconds": s, "count": c}
                               for n, (s, c) in v.items()}
                           for k, v in by_prog.items()},
        "device_ops": [[n, s] for n, s in sorted(
            totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def main(argv: Sequence[str]) -> int:
    if len(argv) != 2:
        print(__doc__.split("Run as a script")[1], file=sys.stderr)
        return 2
    path = argv[1] if argv[1].endswith(".pb") else find_xplane(argv[1])
    planes = load_xplane(path, device_only=False)
    for plane, lines in planes.items():
        print(f"PLANE {plane}")
        for line, evs in lines.items():
            names: Dict[str, int] = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            some = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {line!r}: {len(evs)} events; {some}")
    red = reduce_trace({k: v for k, v in planes.items()
                        if k.startswith(DEVICE_PREFIX)})
    red.pop("ops_by_program", None)
    print(json.dumps(red, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
