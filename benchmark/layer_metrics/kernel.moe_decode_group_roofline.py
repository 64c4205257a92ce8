"""Roofline share of the expert layers of one decode step (memory bound),
for a stack of Ling's key names (``num_experts`` held of
``router_num_experts`` scored in ``n_group`` groups, ``num_shared_experts``
shared experts of ``moe_shared_expert_intermediate_size``) behind
``first_k_dense_replace`` leading dense layers:
``kernel.moe_decode_held_roofline``'s rule under other keys (that file reads
DeepSeek's ``router_n_routed_experts`` / ``n_routed_experts`` /
``n_shared_experts`` and finds nothing here).

The yardstick is the model's need from the configuration's published keys and
an exact count of the program's: one execution of the width-1 step program
must, at the least, read once, in the served dtype, the three projections of
every held expert that the step's routing TOUCHED
(``host_stats.moe_experts_touched`` over the steps fetched in the window:
``host_stats.moe_expert_slots`` / (held experts x expert layers)), and per
expert layer the router over ALL the columns it scores and the shared expert.
An expert no token was routed to, the selection bias and activations are the
program's overhead, not the algorithm's need.

The time is the device self time under the scope ``moe`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``). Nothing to read (a program
without the counters, no ``moe`` scope, a configuration without
``router_num_experts`` and ``moe_shared_expert_intermediate_size``:
Qwen3-Next's file has the first and not the second): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def expert_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def moe_group_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL expert layers of one decode step must read when its routing
    touches ``touched_per_step`` held experts, summed over the layers."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    expert = 3 * hid * cfg["moe_intermediate_size"]
    shared = 3 * hid * cfg.get("num_shared_experts", 1) \
        * cfg["moe_shared_expert_intermediate_size"]
    a_layer = hid * cfg["router_num_experts"] + shared
    return (touched_per_step * expert + expert_layers(cfg) * a_layer) * size


def read(ctx):
    cfg = ctx["config"]
    if not cfg.get("router_num_experts") \
            or not cfg.get("moe_intermediate_size") \
            or "moe_shared_expert_intermediate_size" not in cfg:
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["num_experts"] * expert_layers(cfg))
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    least_s = moe_group_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
