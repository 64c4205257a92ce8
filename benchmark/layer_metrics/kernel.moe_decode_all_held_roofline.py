"""Roofline share of the expert layers of one decode step (memory bound),
for a stack that holds EVERY expert of its expert layers and whose first
``num_dense_layers`` layers are dense.

The yardstick is computed here from the configuration's published keys
(``num_experts``, ``moe_intermediate_size``, ``num_dense_layers``) and from an
exact count of the program's, not from what the program reads. One execution
of the width-1 step program must, at the least, read once, in the served
dtype: the three projections of every expert that the step's routing TOUCHED
(an expert that received at least one token of a live row:
``host_stats.moe_experts_touched``, summed on the device over the expert
layers and fetched with the tokens, over the steps fetched in the window:
``host_stats.moe_expert_slots`` / (experts x expert layers)), and every
expert layer's router and selection bias. The leading dense layers' MLPs
(their time lies under ``mlp``), an expert no live row picked, activations and
whatever else the program touches are its overhead or its opportunity, not the
count: a walk that reads only the touched experts cannot read over 100 %.

The time is the device self time under the scope ``moe`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``). Nothing to read (a program
without the counters, no ``moe`` scope, a configuration without
``num_dense_layers`` / ``moe_intermediate_size``): None.
``kernel.moe_decode_experts_roofline`` is the counterpart under SmallThinker's
key names."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def expert_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def moe_all_held_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL expert layers of one decode step must read when its routing
    touches ``touched_per_step`` experts, summed over the layers."""
    hid, n_e = cfg["hidden_size"], cfg["num_experts"]
    expert = 3 * hid * cfg["moe_intermediate_size"]
    routers = expert_layers(cfg) * (hid * n_e + n_e)
    return (touched_per_step * expert + routers) * DTYPE_BYTES[cfg["dtype"]]


def read(ctx):
    cfg = ctx["config"]
    if cfg.get("num_dense_layers") is None or not cfg.get("num_experts") \
            or not cfg.get("moe_intermediate_size"):
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["num_experts"] * max(expert_layers(cfg), 1))
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    least_s = moe_all_held_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
