"""Roofline share of the Mamba-2 mixers of one decode step (memory bound):
``kernel.mixer_decode_roofline``'s count under Nemotron-H's key names
(``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``conv_kernel``,
``n_groups``, the ``M`` of ``hybrid_override_pattern``), for a mixer with
several B / C groups whose inner width is ``mamba_num_heads x mamba_head_dim``
(not ``expand x hidden_size``).

The yardstick is the MODEL's need, computed here from the published shapes,
not read from the program. One execution of the width-1 step program must, at
the least, per mixer layer: read the layer's mixer weights once (in-projection,
depthwise convolution and its bias, ``dt_bias`` / ``A_log`` / ``D``, the gated
norm, the out-projection and the layer's own norm, in the served dtype) and
read AND write each live row's recurrent state once (the SSM state in float32,
the ``conv_kernel - 1`` carried convolution inputs in the served dtype).
Activations, dead rows and whatever else the program touches are its overhead.
One read and one write stream reach ~650 GB/s together on this chip (PERF.md
section 3), so ~80-85 % is the honest ceiling of a mix of weights and state.

The time is the device self time under the scope ``mixer`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``); live rows are the mean of their
values at the two edges of the profiled slice. Nothing to read (no ``mixer``
scope, a configuration without these keys or without an ``M`` layer): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def mixer_groups_min_bytes(cfg, rows: float) -> float:
    """Bytes ALL mixer layers of one decode step must move at ``rows`` live
    rows."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    heads, head_dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    state, conv_k = cfg["ssm_state_size"], cfg["conv_kernel"]
    d_inner = heads * head_dim
    conv_dim = d_inner + 2 * cfg["n_groups"] * state
    weights = (hid * (d_inner + conv_dim + heads)       # in_proj
               + conv_dim * conv_k + conv_dim           # conv1d, its bias
               + 3 * heads + d_inner + hid              # dt, A, D; norms
               + d_inner * hid) * size                  # out_proj
    row_state = (heads * head_dim * state * 4           # SSM state, float32
                 + conv_dim * (conv_k - 1) * size)      # conv tails
    layers = cfg["hybrid_override_pattern"].count("M")
    return layers * (weights + rows * 2 * row_state)


def read(ctx):
    cfg, edges = ctx["config"], ctx["slice"]
    if "mamba_num_heads" not in cfg \
            or "M" not in cfg.get("hybrid_override_pattern", "") \
            or not edges.get("before") or not edges.get("after"):
        return None
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "mixer")
    if not ms:
        return None
    rows = (edges["before"]["counters"]["kv.live_rows"]
            + edges["after"]["counters"]["kv.live_rows"]) / 2.0
    least_s = mixer_groups_min_bytes(cfg, rows) / (
        ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
