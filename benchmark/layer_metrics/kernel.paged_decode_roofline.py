"""Roofline share of the paged decode attention kernel (memory bound).

The trace names the Mosaic call apart: its operations on the ``XLA Ops`` line
are ``paged_decode_attention[.n]`` (one call per layer inside the width-1
step program). Live KV tokens and live rows are the mean of their values at
the two edges of the profiled slice (the slice is ~3 s of steady load)."""

from harness.kernel_bytes import paged_decode_min_bytes

KERNEL = "paged_decode_attention"


def read(ctx):
    trace, edges = ctx.get("trace"), ctx["slice"]
    if not trace or not edges.get("before") or not edges.get("after"):
        return None
    ops = trace.get("ops_by_program", {}).get("paged.w1", {})
    calls = [v for name, v in ops.items() if name.split(".")[0] == KERNEL]
    seconds = sum(v["seconds"] for v in calls)
    count = sum(v["count"] for v in calls)
    if not count or seconds <= 0:
        return None

    def mean_of(key):
        return (edges["before"]["counters"][key]
                + edges["after"]["counters"][key]) / 2.0
    rows = mean_of("kv.live_rows")
    need = paged_decode_min_bytes(ctx["config"], mean_of("kv.live_tokens"),
                                  rows)
    least_s = need / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (seconds / count)
