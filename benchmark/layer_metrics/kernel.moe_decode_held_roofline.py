"""Roofline share of the expert layers of one decode step (memory bound),
for a stack of DeepSeek's key names that holds a SHARE of its routed experts
behind ``first_k_dense_replace`` leading dense layers.

The yardstick is computed here from the configuration's published keys and
from an exact count of the program's, not from what the program reads. One
execution of the width-1 step program must, at the least, read once, in the
served dtype: the three projections of every held expert that the step's
routing TOUCHED (a held expert that received at least one token of a live
row: ``host_stats.moe_experts_touched``, summed on the device over the
expert layers and fetched with the tokens, over the steps fetched in the
window: ``host_stats.moe_expert_slots`` / (held experts x expert layers)),
and per expert layer the router over ALL the columns it scores
(``router_n_routed_experts``) and the shared experts. An expert no token was
routed to, the selection bias, activations and whatever else the program
touches are its overhead, not the algorithm's need: a walk that reads only
the touched experts cannot read over 100 %.

The time is the device self time under the scope ``moe`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``). Nothing to read (a program
without the counters, no ``moe`` scope, a configuration without
``router_n_routed_experts`` / ``moe_intermediate_size``): None.
``kernel.moe_decode_roofline`` reads qwen3-next's key names and
``kernel.moe_decode_experts_roofline`` a stack that holds every expert."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def expert_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def moe_held_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL expert layers of one decode step must read when its routing
    touches ``touched_per_step`` held experts, summed over the layers."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    expert = 3 * hid * cfg["moe_intermediate_size"]
    a_layer = (hid * cfg["router_n_routed_experts"]              # router
               + cfg.get("n_shared_experts", 0) * expert)        # shared
    return (touched_per_step * expert + expert_layers(cfg) * a_layer) * size


def read(ctx):
    cfg = ctx["config"]
    if not cfg.get("router_n_routed_experts") \
            or not cfg.get("moe_intermediate_size"):
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["n_routed_experts"] * expert_layers(cfg))
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    least_s = moe_held_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
