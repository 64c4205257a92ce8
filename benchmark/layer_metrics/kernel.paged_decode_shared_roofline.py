"""Roofline share of the paged decode attention calls of one decode step of a
decoder-hybrid-decoder (``model_type`` ``phi4flash``): ONE full-attention
layer's pool is attended over by that layer and by every cross-attention
layer of the second decoder, a row's every token each time; the window layers
of the first decoder attend inside ``sliding_window`` (memory bound).

The trace names the Mosaic call apart: its operations on the ``XLA Ops`` line
are ``paged_decode_attention[.n]``, one a layer of the unrolled width-1 step
program. The yardstick is computed here from the configuration's published
keys (:func:`readers_and_rings`) and two gauges the adapter sets at each
decode dispatch - the running rows' tokens (``kv_tokens_running``) and their
tokens inside the window (``kv_tokens_in_window``: sum of ``min(length,
sliding_window)``) - the mean of their values at the two edges of the
profiled slice (~3 s of steady load): every reader of the shared pool must
read all of the first, every window layer's call the second, and each call
its rows' queries and outputs (``harness/kernel_bytes.py``). What the kernel
reads beyond that (a page in front of the window, block padding, the table)
and what the pools hold are the program's overhead, not the model's need. If
the served attention were another operation than this kernel, ``KERNEL``
would name it: the need is the model's."""

from harness.kernel_bytes import paged_decode_min_bytes

KERNEL = "paged_decode_attention"


def readers_and_rings(cfg):
    """``(layers that attend over the one full-length pool, window layers)``
    from the published keys, or None for another architecture. With ``N``
    layers and ``mb_per_layer`` 2: layer ``N / 2 + 1`` attends over
    everything and the odd layers above it read ITS keys and values; the odd
    layers below ``N / 2`` attend inside the window."""
    if cfg.get("model_type") != "phi4flash" or cfg.get("mb_per_layer") != 2:
        return None
    n = cfg["num_hidden_layers"]
    half = n // 2
    readers = 1 + sum(1 for l in range(half + 2, n) if l % 2)
    rings = sum(1 for l in range(half) if l % 2)
    return readers, rings


def read(ctx):
    cfg = ctx["config"]
    trace, edges = ctx.get("trace"), ctx["slice"]
    kinds = readers_and_rings(cfg)
    if kinds is None or not trace \
            or not edges.get("before") or not edges.get("after"):
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
    readers, rings = kinds
    rows = mean_of("kv.live_rows")
    need = (readers * paged_decode_min_bytes(cfg, mean_of(gauges[0]), rows)
            + rings * paged_decode_min_bytes(cfg, mean_of(gauges[1]), rows))
    steps = count / (readers + rings)
    least_s = need / (ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (seconds / steps)
