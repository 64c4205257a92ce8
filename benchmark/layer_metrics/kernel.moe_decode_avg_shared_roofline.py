"""Roofline share of the expert work of one decode step (memory bound), for
a PARALLEL block of cohere2_moe's key names that holds a SHARE of its routed
experts beside AVERAGED shared experts.

The yardstick is computed here from the configuration's published keys and
from an exact count of the program's, not from what the program reads. One
execution of the width-1 step program must, at the least, read once, in the
served dtype: the three projections of every held expert that the step's
routing TOUCHED (a held expert that received at least one token of a live
row: ``host_stats.moe_experts_touched``, summed on the device over the
layers and fetched with the tokens, over the steps fetched in the window:
``host_stats.moe_expert_slots`` / (held experts x layers)), and per layer
the router over ALL the columns it scores (``router_num_experts``) and the
``num_shared_experts`` shared experts, each of ``intermediate_size``. An
expert no token was routed to, activations and whatever else the program
touches are its overhead, not the algorithm's need: the share cannot pass
100 %.

The time is the device self time per execution of ``paged.w1`` under the
scope ``moe`` (router, walk, combine) PLUS the scope ``shared`` (the averaged
shared experts, a sibling stream of a parallel block): the same work
whichever scope a program files the shared experts under. Nothing to read (a
program without the counters, no ``moe`` scope, a configuration without
``router_num_experts`` / ``num_shared_experts`` /
``shared_expert_combination_strategy``): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def moe_avg_shared_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL layers' expert work of one decode step must read when its
    routing touches ``touched_per_step`` held experts, summed over the
    layers."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    expert = 3 * hid * cfg["intermediate_size"]
    a_layer = (hid * cfg["router_num_experts"]                   # router
               + cfg["num_shared_experts"] * expert)             # shared
    return (touched_per_step * expert
            + cfg["num_hidden_layers"] * a_layer) * size


def read(ctx):
    cfg = ctx["config"]
    if not cfg.get("router_num_experts") \
            or not cfg.get("num_shared_experts") \
            or "shared_expert_combination_strategy" not in cfg:
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["num_experts"] * cfg["num_hidden_layers"])
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    ms += host_spans.program_scope_ms(ctx, "paged", 1, "shared") or 0.0
    least_s = moe_avg_shared_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
