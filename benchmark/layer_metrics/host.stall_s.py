"""Seconds of slices that ran long over the window, every span; 0.0 where
nothing stalled (the counter is made at the first stall) or the program has
no such counter."""

STALL_SECONDS = "nxdi_host_stall_seconds_total"


def read(ctx):
    def by_span(snap):
        rows = snap.get("prom", {}).get(STALL_SECONDS, {}).get("series", [])
        return {s["labels"].get("span", ""): float(s["value"]) for s in rows}
    after, before = by_span(ctx["after"]), by_span(ctx["before"])
    delta = {k: v - before.get(k, 0.0) for k, v in after.items()}
    stalled = {k: v for k, v in delta.items() if v > 0}
    if stalled:
        print("[host.stall_s] slices of 2 s or more over the window, s by "
              f"innermost span: {stalled}", flush=True)
    return sum(stalled.values())
