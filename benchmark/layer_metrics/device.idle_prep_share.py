"""Device idle time while a ``prep.*`` span (the phases of ``run.paged``)
was open on the host, by phase in the printed line.

``host_spans.load_planes`` keeps only the span names it knows, so the host
plane is read here, from the same xplane, through ``xplane_wire``."""

from harness import host_spans, reduce_trace, xplane_wire

PHASES = ("prep.inputs", "prep.rng", "prep.enqueue")


def prep_events(path):
    """The ``prep.*`` TraceMe events of the host plane, whatever thread line
    they are on."""
    planes = xplane_wire.read_planes(
        path, want_plane=lambda p: p == host_spans.HOST_PLANE,
        want_line=lambda p, ln: True)
    return [e for line in planes.get(host_spans.HOST_PLANE, {}).values()
            for e in line if e.name in PHASES]


def idle_by_phase(planes, events):
    """``{phase: seconds}`` of device idle time (mean over chips) inside the
    events of each phase, with ``window_s``; None without a device plane or
    without such events. The window and the idle intervals are
    ``host_spans.idle_by_span``'s."""
    dev = reduce_trace.device_planes(planes)
    if not dev or not events:
        return None
    ops = reduce_trace.OPS_LINE
    lo = min(e.start for p in dev for e in planes[p][ops])
    hi = max(e.end for p in dev for e in planes[p][ops])
    spans = {ph: host_spans.merge((e.start, e.end) for e in events
                                  if e.name == ph) for ph in PHASES}
    out = {ph: 0.0 for ph in PHASES}
    for p in dev:
        idle = host_spans.complement(
            host_spans.merge((e.start, e.end) for e in planes[p][ops]),
            lo, hi)
        for ph, iv in spans.items():
            out[ph] += host_spans.seconds(
                host_spans.intersect(idle, iv)) / len(dev)
    out["window_s"] = hi - lo
    return out


def read(ctx):
    got = host_spans.load_slice(ctx)
    trace_dir = host_spans.slice_trace_dir(ctx)
    if not got or trace_dir is None:
        return None
    split = idle_by_phase(got["planes"], prep_events(
        reduce_trace.find_xplane(trace_dir)))
    if not split or split["window_s"] <= 0:
        return None
    pct = {ph: 100.0 * split[ph] / split["window_s"] for ph in PHASES}
    print("[device.idle_prep_share] device idle inside run.paged's phases, "
          "% of the slice: "
          + ", ".join(f"{ph} {v:.3f}" for ph, v in pct.items()), flush=True)
    return sum(pct.values())
