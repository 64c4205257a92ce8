"""Roofline share of the channel-gated delta-rule (Kimi Delta Attention)
layers of one decode step (memory bound).

The yardstick is the MODEL's need whatever implements it, computed here from
the configuration's published keys, not read from the program. One execution
of the width-1 step program must, at the least, per linear layer (every layer
``l`` with ``(l + 1) % layer_group_size != 0``): read the block's weights once
(the q, k and v projections, the decay's full-rank projection, the write
strength and the head-wise gate, the three depthwise convolutions, ``A_log``,
``dt_bias``, the output norm, the out-projection, in the served dtype) and
read AND write each live row's recurrent state once (the ``(head_dim,
head_dim)`` matrix of every head in float32, the ``kernel - 1`` carried
convolution inputs over q, k and v in the served dtype). Activations, dead
rows and whatever else the program touches are its overhead, not the
algorithm's need. One read and one write stream reach ~650 GB/s together on
this chip (PERF.md section 7), so a sound program cannot pass ~85 %.

The time is the device self time under the scope ``mixer`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``); live rows are the mean of
their values at the two edges of the profiled slice, as
``kernel.gdn_decode_roofline`` takes them. Nothing to read (a program with no
``mixer`` scope, a configuration without ``kda_lower_bound``): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def kda_layers(cfg) -> int:
    period = cfg["layer_group_size"]
    return sum((l + 1) % period != 0
               for l in range(cfg["num_hidden_layers"]))


def kda_block_weights(cfg) -> int:
    """Parameters of ONE linear layer's temporal block."""
    hid = cfg["hidden_size"]
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    wide, conv_k = heads * d, cfg["short_conv_kernel_size"]
    return (hid * 3 * wide                  # q, k, v
            + hid * wide                    # the decay, full rank
            + hid * 2 * heads               # write strength, head-wise gate
            + 3 * wide * conv_k             # three conv1d
            + heads + wide + d              # A_log, dt_bias, the output norm
            + wide * hid)                   # o_proj


def kda_row_state_bytes(cfg) -> int:
    """Bytes of ONE row's state and conv tails, a linear layer."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    return (heads * d * d * 4
            + 3 * heads * d * (cfg["short_conv_kernel_size"] - 1)
            * DTYPE_BYTES[cfg["dtype"]])


def kda_decode_min_bytes(cfg, rows: float) -> float:
    """Bytes ALL linear layers of one decode step must move at ``rows`` live
    rows."""
    return kda_layers(cfg) * (
        kda_block_weights(cfg) * DTYPE_BYTES[cfg["dtype"]]
        + rows * 2 * kda_row_state_bytes(cfg))


def read(ctx):
    cfg, edges = ctx["config"], ctx["slice"]
    if "kda_lower_bound" not in cfg or "layer_group_size" not in cfg \
            or not edges.get("before") or not edges.get("after"):
        return None
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "mixer")
    if not ms:
        return None
    rows = (edges["before"]["counters"]["kv.live_rows"]
            + edges["after"]["counters"]["kv.live_rows"]) / 2.0
    least_s = kda_decode_min_bytes(cfg, rows) / (
        ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
