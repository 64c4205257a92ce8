"""Roofline share of the expert layers of one decode step (memory bound),
for a stack that holds a SHARE of its expert layers.

The yardstick is computed here from the configuration's published keys and
from an exact count of the program's, not from what the program reads. One
execution of the width-1 step program must, at the least, read once, in the
served dtype: the three projections of every held expert that the step's
routing TOUCHED (a held expert that received at least one token of a live
row: ``host_stats.moe_experts_touched``, summed on the device over the
expert layers and fetched with the tokens, over the steps fetched in the
window: ``host_stats.moe_expert_slots`` / (held experts x expert layers)),
and every expert layer's router (over all the experts it scores), shared
expert and the shared expert's gate. An expert no token was routed to,
activations and whatever else the program touches are its overhead, not the
algorithm's need: a step that streams all held experts reads under the
share of them its routing touches, and a later change that reads only those
cannot read over 100 %.

The time is the device self time under the scope ``moe`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``). Nothing to read (a program
without the counters, no ``moe`` scope, a configuration that holds every
expert it routes over): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def moe_decode_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL expert layers of one decode step must read when its routing
    touches ``touched_per_step`` held experts, summed over the layers."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    expert = 3 * hid * cfg["moe_intermediate_size"]
    routed = cfg.get("router_num_experts") or cfg["num_experts"]
    a_layer = (hid * routed                                      # router
               + 3 * hid * cfg["shared_expert_intermediate_size"]
               + hid)                                            # its gate
    return (touched_per_step * expert
            + cfg["num_hidden_layers"] * a_layer) * size


def read(ctx):
    cfg = ctx["config"]
    if not cfg.get("router_num_experts"):
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["num_experts"] * cfg["num_hidden_layers"])
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    least_s = moe_decode_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
