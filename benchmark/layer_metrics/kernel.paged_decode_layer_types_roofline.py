"""Roofline share of the paged decode attention calls of one decode step of a
stack whose layers are of two kinds BY ``layer_types``: ``full_attention``
layers attend over a row's every token, ``sliding_attention`` layers inside
``sliding_window`` (memory bound). The counterpart of
``kernel.paged_decode_window_roofline`` (SmallThinker's
``sliding_window_layout``) for the key names of Cohere's second generation.

The trace names the Mosaic call apart: its operations on the ``XLA Ops`` line
are ``paged_decode_attention[.n]``, one a layer inside the width-1 step
program (an op of a scanned period runs once a period a step). The yardstick
is computed here from the configuration's published keys and two gauges the
adapter sets at each decode dispatch - the running rows' tokens
(``kv_tokens_running``) and their tokens inside the window
(``kv_tokens_in_window``: sum of ``min(length, sliding_window)``) - the mean
of their values at the two edges of the profiled slice: every full layer's
call must read all of the first, every sliding layer's call the second, and
each call its rows' queries and outputs (``harness/kernel_bytes.py``). What
the kernel reads beyond that (a page in front of the window, block padding,
the table, a score tile wider than a head's columns) is the program's
overhead, not the model's need: the share cannot pass 100 %."""

from harness.kernel_bytes import paged_decode_min_bytes

KERNEL = "paged_decode_attention"


def layer_types_decode_need(cfg, running: float, in_window: float,
                            rows: float) -> float:
    """Bytes ALL the decode attention calls of one step must move, for
    ``rows`` running rows of ``running`` tokens of which ``in_window`` lie
    inside their row's window."""
    n_window = sum(1 for t in cfg["layer_types"] if t == "sliding_attention")
    n_full = len(cfg["layer_types"]) - n_window
    return (n_full * paged_decode_min_bytes(cfg, running, rows)
            + n_window * paged_decode_min_bytes(cfg, in_window, rows))


def read(ctx):
    cfg = ctx["config"]
    trace, edges = ctx.get("trace"), ctx["slice"]
    if "layer_types" not in cfg or not cfg.get("sliding_window") \
            or not trace or not edges.get("before") or not edges.get("after"):
        return None
    gauges = ("host_stats.kv_tokens_running", "host_stats.kv_tokens_in_window")
    if any(g not in edges[e]["counters"] for g in gauges
           for e in ("before", "after")):
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
    need = layer_types_decode_need(cfg, mean_of(gauges[0]),
                                   mean_of(gauges[1]),
                                   mean_of("kv.live_rows"))
    steps = count / len(cfg["layer_types"])
    least_s = need / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (seconds / steps)
