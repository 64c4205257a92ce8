"""Roofline share of the Mamba-2 mixers of one decode step (memory bound).

The yardstick is computed here from the configuration's published shapes,
not read from the program. One execution of the width-1 step program must, at
the least, per mixer layer: read the layer's mixer weights once (in-projection,
depthwise convolution and its bias, ``dt_bias`` / ``A_log`` / ``D``, the gated
norm, the out-projection, in the served dtype) and read AND write each live
row's recurrent state once (the SSM state in float32, the ``d_conv - 1``
carried convolution inputs in the served dtype). Activations, dead rows and
whatever else the program touches are its overhead, not the algorithm's need.

The time is the device self time under the scope ``mixer`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``); live rows are the mean of
their values at the two edges of the profiled slice, as
``kernel.paged_decode_roofline`` takes them. Nothing to read (a program with
no ``mixer`` scope, a configuration with no mixer layer): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def mixer_decode_min_bytes(cfg, rows: float) -> float:
    """Bytes ALL mixer layers of one decode step must move at ``rows`` live
    rows."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    d_inner = int(cfg["mamba_expand"] * hid)
    heads, head_dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    state, conv_k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    conv_dim = d_inner + 2 * cfg["mamba_n_groups"] * state
    weights = (hid * (d_inner + conv_dim + heads)       # in_proj
               + conv_dim * conv_k + conv_dim           # conv1d, its bias
               + 3 * heads + d_inner                    # dt_bias, A_log, D; norm
               + d_inner * hid) * size                  # out_proj
    row_state = (heads * head_dim * state * 4           # SSM state, float32
                 + conv_dim * (conv_k - 1) * size)      # conv tails
    layers = sum(t == "mamba" for t in cfg["layer_types"])
    return layers * (weights + rows * 2 * row_state)


def read(ctx):
    cfg, edges = ctx["config"], ctx["slice"]
    if "mamba_n_heads" not in cfg or not edges.get("before") \
            or not edges.get("after"):
        return None
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "mixer")
    if not ms:
        return None
    rows = (edges["before"]["counters"]["kv.live_rows"]
            + edges["after"]["counters"]["kv.live_rows"]) / 2.0
    least_s = mixer_decode_min_bytes(cfg, rows) / (
        ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
