"""Roofline share of the gated short convolutions of one decode step (memory
bound).

The yardstick is computed here from the configuration's published shapes, not
read from the program. One execution of the width-1 step program must, at the
least, per ``conv`` layer of ``layer_types``: read the layer's weights once
(``in_proj`` hidden x 3 hidden, the depthwise taps hidden x ``conv_L_cache``,
``out_proj`` hidden x hidden, in the served dtype) and read AND write each
live row's conv tail once (``conv_L_cache - 1`` products a channel, in the
served dtype). Activations, dead rows and whatever else the program touches
are its overhead, not the algorithm's need; the count is the same whether XLA
fusions or a kernel do the work.

The time is the device self time under the scope ``mixer`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``); live rows are the mean of
their values at the two edges of the profiled slice, as
``kernel.paged_decode_roofline`` takes them. Nothing to read (a program with
no ``mixer`` scope, a configuration with no ``conv_L_cache`` or no conv
layer): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def shortconv_decode_min_bytes(cfg, rows: float) -> float:
    """Bytes ALL conv layers of one decode step must move at ``rows`` live
    rows."""
    hid, k = cfg["hidden_size"], cfg["conv_L_cache"]
    size = DTYPE_BYTES[cfg["dtype"]]
    weights = (hid * 3 * hid + hid * k + hid * hid) * size
    row_tail = hid * (k - 1) * size
    layers = sum(t == "conv" for t in cfg["layer_types"])
    return layers * (weights + rows * 2 * row_tail)


def read(ctx):
    cfg, edges = ctx["config"], ctx["slice"]
    if not cfg.get("conv_L_cache") or "conv" not in cfg.get(
            "layer_types", ()) or not edges.get("before") \
            or not edges.get("after"):
        return None
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "mixer")
    if not ms:
        return None
    rows = (edges["before"]["counters"]["kv.live_rows"]
            + edges["after"]["counters"]["kv.live_rows"]) / 2.0
    least_s = shortconv_decode_min_bytes(cfg, rows) / (
        ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
