"""Of the time requests spent between their admission and their first token,
the share in which the device ran no operation.

The program leaves two zero-length TraceMe marks a request on the host plane
of the slice's xplane, ``request.admit`` and ``request.token`` (stat
``trace``: the request's trace id), at the two boundaries of its timeline
that enclose its prefill: picked for admission, and first token
host-visible. ``host_spans.load_planes`` keeps only the span names it knows,
so the host plane is read here, from the same file, through ``xplane_wire``
(as ``device.idle_prep_share.py`` does)."""

from harness import host_spans, reduce_trace, xplane_wire

MARKS = ("request.admit", "request.token")


def mark_events(path):
    """The two marks' events of the host plane, whatever thread line they
    are on, with their ``trace`` stat."""
    planes = xplane_wire.read_planes(
        path, want_plane=lambda p: p == host_spans.HOST_PLANE,
        want_line=lambda p, ln: True, keep_stats=("trace",))
    return [e for line in planes.get(host_spans.HOST_PLANE, {}).values()
            for e in line if e.name in MARKS]


def prefill_intervals(marks):
    """``[(admit, token)]`` of the requests whose marks both lie in the
    slice, paired by the ``trace`` stat: a token mark with the latest admit
    mark of its trace before it (an admission that was rolled back and made
    again leaves two). A request admitted before the slice, or answered
    after it, has one mark here and gives nothing."""
    admits, out = {}, []
    for e in sorted(marks, key=lambda e: e.start):
        key = str(e.stats.get("trace", ""))
        if e.name == MARKS[0]:
            admits[key] = e.start
        elif key in admits:
            out.append((admits.pop(key), e.start))
    return out


def idle_inside(planes, intervals):
    """``(idle seconds, seconds)`` of the union of ``intervals``: in how
    much of it no ``XLA Ops`` event ran (mean over chips), and its length;
    None without a device plane or without an interval."""
    dev = reduce_trace.device_planes(planes)
    union = host_spans.merge(intervals)
    if not dev or not union:
        return None
    idle = 0.0
    for p in dev:
        busy = host_spans.merge((e.start, e.end)
                                for e in planes[p][reduce_trace.OPS_LINE])
        idle += host_spans.seconds(host_spans.subtract(union, busy)) / len(dev)
    return idle, host_spans.seconds(union)


def read(ctx):
    got = host_spans.load_slice(ctx)
    trace_dir = host_spans.slice_trace_dir(ctx)
    if not got or trace_dir is None:
        return None
    pairs = prefill_intervals(mark_events(reduce_trace.find_xplane(trace_dir)))
    inside = idle_inside(got["planes"], pairs)
    if inside is None or inside[1] <= 0:
        return None
    idle_s, union_s = inside
    print(f"[ttft.device_idle_share] {len(pairs)} requests admitted and "
          f"answered inside the slice: admit -> token {union_s * 1e3:.3f} ms "
          f"in all (mean {sum(b - a for a, b in pairs) / len(pairs) * 1e3:.3f}"
          f" a request), the device idle in {idle_s * 1e3:.3f} ms of it",
          flush=True)
    return 100.0 * idle_s / union_s
