"""Roofline share of the expert layers of one decode step (memory bound),
for a stack that holds EVERY expert of every layer.

The yardstick is computed here from the configuration's published keys and
from an exact count of the program's, not from what the program reads. One
execution of the width-1 step program must, at the least, read once, in the
served dtype: the three projections of every expert that the step's routing
TOUCHED (an expert that received at least one token of a live row:
``host_stats.moe_experts_touched``, summed on the device over the layers and
fetched with the tokens, over the steps fetched in the window:
``host_stats.moe_expert_slots`` / (experts x layers)), and every layer's
router. An expert no live row picked, the zeros a ReLU gate leaves,
activations and whatever else the program touches are its overhead or its
opportunity, not the count: a walk that reads only the touched experts
cannot read over 100 %.

The time is the device self time under the scope ``moe`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``): the walk over the touched
experts, the router (in front of attention where the model places it there)
and the combine. Nothing to read (a program without the counters, no ``moe``
scope, a configuration without ``moe_num_primary_experts`` /
``moe_ffn_hidden_size``): None. ``kernel.moe_decode_roofline`` is the
counterpart for a stack that holds a share of its experts."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def moe_stack_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL expert layers of one decode step must read when its routing
    touches ``touched_per_step`` experts, summed over the layers."""
    hid = cfg["hidden_size"]
    expert = 3 * hid * cfg["moe_ffn_hidden_size"]
    routers = cfg["num_hidden_layers"] * hid * cfg["moe_num_primary_experts"]
    return (touched_per_step * expert + routers) * DTYPE_BYTES[cfg["dtype"]]


def read(ctx):
    cfg = ctx["config"]
    if not cfg.get("moe_num_primary_experts") \
            or not cfg.get("moe_ffn_hidden_size"):
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["moe_num_primary_experts"] * cfg["num_hidden_layers"])
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    least_s = moe_stack_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
