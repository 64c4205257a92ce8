"""Roofline share of the expert layers of one decode step (memory bound), for
a share of PLAIN experts (two matrices an expert, ``down(act(up(x)))``) under
a router that scores more experts than the chip holds, with one shared expert
(Nemotron-H's key names).

The yardstick is the MODEL's need, the same whatever implements it, computed
here from the configuration's published keys (``n_routed_experts`` held of
``router_n_routed_experts`` scored, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, the ``E`` of
``hybrid_override_pattern``) and from an exact count of the program's. One
execution of the width-1 step program must, at the least, read once, in the
served dtype: the TWO projections, at the PUBLISHED width whatever width the
program stores them at, of every held expert that the step's routing TOUCHED
(an expert that received at least one token of a live row:
``host_stats.moe_experts_touched``, summed on the device over the expert layers
and fetched with the tokens, over the steps fetched in the window:
``host_stats.moe_expert_slots`` / (held experts x expert layers)); and every
expert layer's router (over all the scored columns), selection bias and shared
expert (two projections). An expert no live row picked, the pad of a stored
width, activations and whatever else the program touches are its overhead or
its opportunity, not the count: a walk that reads only what it must cannot
read over 100 %. ``kernel.moe_decode_held_roofline`` counts THREE projections
an expert under DeepSeek's names and would read ~150 % of a two-matrix walk.

The time is the device self time under the scope ``moe`` per execution of
``paged.w1`` (``host_spans.program_scope_ms``). Nothing to read (a program
without the counters, no ``moe`` scope, a configuration without
``hybrid_override_pattern`` / ``moe_intermediate_size``): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def expert_layers(cfg) -> int:
    return cfg["hybrid_override_pattern"].count("E")


def moe_plain_min_bytes(cfg, touched_per_step: float) -> float:
    """Bytes ALL expert layers of one decode step must read when its routing
    touches ``touched_per_step`` held experts, summed over the layers."""
    hid = cfg["hidden_size"]
    scored = cfg.get("router_n_routed_experts") or cfg["n_routed_experts"]
    expert = 2 * hid * cfg["moe_intermediate_size"]
    shared = 2 * hid * cfg.get("n_shared_experts", 1) * cfg.get(
        "moe_shared_expert_intermediate_size", 0)
    fixed = expert_layers(cfg) * (hid * scored + scored + shared)
    return (touched_per_step * expert + fixed) * DTYPE_BYTES[cfg["dtype"]]


def read(ctx):
    cfg = ctx["config"]
    if not cfg.get("hybrid_override_pattern") \
            or not cfg.get("n_routed_experts") \
            or not cfg.get("moe_intermediate_size") \
            or not expert_layers(cfg):
        return None

    def delta(key):
        return (ctx["after"]["counters"].get("host_stats." + key, 0.0)
                - ctx["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("moe_expert_slots") / (
        cfg["n_routed_experts"] * expert_layers(cfg))
    ms = host_spans.program_scope_ms(ctx, "paged", 1, "moe")
    if steps <= 0 or not ms:
        return None
    least_s = moe_plain_min_bytes(
        cfg, delta("moe_experts_touched") / steps) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (ms * 1e-3)
