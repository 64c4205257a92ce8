"""Roofline share of the expert layers of one decode step (memory bound),
for a stack of Qwen's key names that holds a SHARE of its routed experts in
every layer (``num_experts`` held of a router over ``router_num_experts``),
with or without a shared expert.

The yardstick is computed here from the configuration's published keys and
from an exact count of the program's, not from what the program reads. One
execution of the width-1 step program must, at the least, read once, in the
served dtype: the three projections of every held expert that the step's
routing TOUCHED (a held expert that received at least one token of a live
row: ``host_stats.moe_experts_touched``, summed on the device over the
layers and fetched with the tokens, over the steps fetched in the window:
``host_stats.moe_expert_slots`` / (held experts x layers)), and per layer
the router over ALL the columns it scores and the shared expert where the
configuration has one. An expert no live row picked, activations and
whatever else the program touches are its overhead, not the model's need: a
walk that reads only the touched experts cannot read over 100 %.

The time is the device self time under the scope ``moe`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``): the router, the walk over
the touched experts (``ops/moe_decode.py`` ``moe_decode_experts``), the
combine. Nothing to read (a program without the counters, no ``moe`` scope,
a configuration without ``router_num_experts`` / ``num_experts`` /
``moe_intermediate_size``): None. ``kernel.moe_decode_held_roofline`` reads
DeepSeek's key names, ``kernel.moe_decode_roofline`` qwen3-next's with its
shared expert, ``kernel.moe_decode_experts_roofline`` a whole stack."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES

KEYS = ("router_num_experts", "num_experts", "moe_intermediate_size")


def moe_share_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL expert layers of one decode step must read when its routing
    touches ``touched_per_step`` held experts, summed over the layers."""
    hid, size = cfg["hidden_size"], DTYPE_BYTES[cfg["dtype"]]
    a_layer = (hid * cfg["router_num_experts"]                   # router
               + 3 * hid * cfg.get("shared_expert_intermediate_size", 0))
    return (touched_per_step * 3 * hid * cfg["moe_intermediate_size"]
            + cfg["num_hidden_layers"] * a_layer) * size


def read(ctx):
    cfg = ctx["config"]
    if not all(cfg.get(k) for k in KEYS):
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["num_experts"] * cfg["num_hidden_layers"])
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    least_s = moe_share_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
