"""Roofline share of the gated delta-rule layers of one decode step (memory
bound).

The yardstick is computed here from the configuration's published keys, not
read from the program. One execution of the width-1 step program must, at the
least, per ``linear_attention`` layer: read the layer's mixer weights once
(the q, k, v and g projections, a and b, the three depthwise convolutions,
``A_log`` and ``dt_bias``, the output norm, the out-projection, in the served
dtype) and read AND write each live row's recurrent state once (the ``(d_k,
d_v)`` matrix of every head in float32, the ``kernel - 1`` carried
convolution inputs over q, k and v in the served dtype). Activations, dead
rows and whatever else the program touches are its overhead, not the
algorithm's need.

The time is the device self time under the scope ``mixer`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``); live rows are the mean of
their values at the two edges of the profiled slice, as
``kernel.mixer_decode_roofline`` takes them. Nothing to read (a program with
no ``mixer`` scope, a configuration with no linear-attention layer): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def gdn_decode_min_bytes(cfg, rows: float) -> float:
    """Bytes ALL gated delta-rule layers of one decode step must move at
    ``rows`` live rows."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    heads = cfg["linear_num_value_heads"]
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    conv_k = cfg["linear_conv_kernel_dim"]
    qk = cfg["linear_num_key_heads"] * d_k
    conv_dim = 2 * qk + heads * d_v
    weights = (hid * (conv_dim + heads * d_v)           # q, k, v, g
               + hid * 2 * heads                        # a, b
               + conv_dim * conv_k                      # three conv1d
               + 2 * heads + d_v                        # A_log, dt_bias; norm
               + heads * d_v * hid) * size              # o_proj
    row_state = (heads * d_k * d_v * 4                  # state, float32
                 + conv_dim * (conv_k - 1) * size)      # conv tail
    layers = sum(t == "linear_attention" for t in cfg["layer_types"])
    return layers * (weights + rows * 2 * row_state)


def read(ctx):
    cfg, edges = ctx["config"], ctx["slice"]
    if "linear_num_value_heads" not in cfg or not edges.get("before") \
            or not edges.get("after"):
        return None
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "mixer")
    if not ms:
        return None
    rows = (edges["before"]["counters"]["kv.live_rows"]
            + edges["after"]["counters"]["kv.live_rows"]) / 2.0
    least_s = gdn_decode_min_bytes(cfg, rows) / (
        ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
