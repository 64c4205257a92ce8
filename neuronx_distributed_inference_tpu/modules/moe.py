"""Mixture-of-Experts block — TPU-native replacement for the reference's NxD
MoE stack (reference: modules/moe_v2.py ``initialize_moe_module`` building
RouterTopK + ExpertMLPsV2 + SharedExperts, and the all-experts decode MoE
kernel ``moe_token_gen`` noted in SURVEY §2.10).

Design:
  * Router: replicated (H, E) matmul in fp32, softmax (or sigmoid for
    DeepSeek-style routers), top-k, optional renormalization and routed
    scaling (reference: MoENeuronConfig knobs, models/config.py:798-846).
  * Experts, dense path: ALL experts compute on all tokens, outputs combined
    with the (B,T,E) routing weights. This mirrors the reference's decode
    all-experts kernel; for the small T of token generation the expert matmuls
    are batched into one einsum that XLA maps onto the MXU. Expert dim shards
    on mesh axis "ep" (moe_ep), intermediate dim on "tp" (moe_tp) — the
    combine-sum over E emits a psum over "ep" automatically.
  * Experts, touched path (a step whose rows fit the kernel's VMEM whole,
    one chip: a decode step, a one-row prefill chunk): the step reads
    only the experts its routing touched, each once. A Pallas kernel
    (``ops/moe_decode.py``) takes the tokens, the expert leaves IN THEIR
    STACK with the layer index and the routing - the combine matrix over
    the held experts for a step of at most one tile of rows, which goes
    whole against every touched expert; the assignments sorted by expert
    for a longer one, where an expert meets ITS rows only - builds the
    touched list on the device and walks it, one expert's three matrices a
    step into one of two VMEM slots. An untouched expert's term of the
    dense sum is exactly zero, so it is the same sum in another order.
    Where the kernel declines (``moe_decode.declined``: quantized, sharded,
    ``input_scaled``, biases, ``tkg_experts_local``, widths that are not
    whole tiles, rows that do not fit VMEM) few tokens keep the dense path
    and a chunk the ragged one, and ``kernel_mode.note("moe_decode", ...)``
    says which and why.
  * Experts, ragged path (a full-batch prefill pack, and every chunk the
    kernel declines): tokens are sorted by expert and run through grouped
    matmuls via ``jax.lax.ragged_dot`` — the dropless TPU-native analog of
    the reference's blockwise matmul (MoENeuronConfig blockwise configs).
    It spends a 512-row tile of the MXU on every non-empty group. Inside
    a layer loop it is handed the STACKED weights and the layer index
    (:class:`LayerOfStack`) and selects the layer through its group sizes:
    on TPU ``ragged_dot`` is a custom call whose operand must be a whole
    buffer, so a slice cut in front of it is a copy of the layer's experts
    on every call.
  * Who takes which is decided in ONE place, :func:`takes_ragged`, from
    what the step shows: its tokens, the spec and the expert leaves.
  * Shared experts (reference: SharedExperts in moe_v2.py:104) are a plain
    dense MLP added to the routed output, behind a per-token sigmoid gate
    where the spec says so (``shared_gated``: Qwen2-MoE / Qwen3-Next).
    Several shared experts are ONE gated MLP over their concatenated width
    (the sum of their outputs); where the architecture AVERAGES them
    (``shared_mean_of``: Command A+'s four) that MLP's output is divided by
    their count. A parallel block runs the branch as a third stream beside
    attention and the routed experts (:func:`shared_experts`, under the
    layer walk's scope ``shared``).
  * A SHARE of a layer (``held_experts``): the router scores all
    ``num_experts`` with the published top-k and renormalisation, the
    weights hold the ``held_experts`` experts from ``first_expert`` on, and
    the block returns their part of the sum only (one chip's part of an
    expert-parallel layer, run without its exchange: what the other chips
    hold is nobody's here). The shared expert is whole on every share.
  * Zero-compute experts (``zero_experts``, LongCat-Flash): the router's
    last columns are identity experts. No stack holds them, so to the three
    expert paths a pick of one is a pick of an expert held elsewhere; the
    block adds ``(sum of the picked identity weights) x input`` itself,
    once, for every row it computes (:func:`zero_expert_term`).

All routing math in fp32 (router logits decide tokens; bf16 tie-breaks
diverge from HF goldens).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import kernel_mode, moe_decode
from ..parallel.mesh import (AXIS_DP, AXIS_EP, AXIS_MP, AXIS_TP,
                             shard_constraint)
from .quantization import dequantize, is_quantized_leaf, qeinsum, qlinear


@dataclass(frozen=True)
class MoESpec:
    """Static MoE architecture description (hashable; closed over by jit)."""

    num_experts: int
    top_k: int
    intermediate_size: int           # per-expert intermediate
    normalize_topk: bool = True      # renormalize top-k affinities
    routed_scaling: Optional[float] = None
    # "softmax" | "sigmoid" | "sparsemixer" (phimoe inference routing)
    router_act: str = "softmax"
    sparsemixer_eps: float = 0.01    # phimoe router_jitter_noise
    pre_softmax_topk: bool = False   # top-k on raw logits, then act over k
    shared_intermediate: int = 0     # 0 = no shared experts
    # the shared branch is the concatenation of this many shared experts
    # whose outputs are AVERAGED (cohere2_moe
    # ``shared_expert_combination_strategy`` "average"): the fused MLP's
    # output is divided by it. 0 = the branch is added as it is (one
    # expert, or several summed)
    shared_mean_of: int = 0
    act: str = "silu"
    # bias added to router scores for expert selection only (DeepSeek-V3
    # e_score_correction_bias); affinity weights still use raw scores
    has_router_bias: bool = False
    # "select": bias affects only which experts win (deepseek);
    # "logits": bias is part of the logits — affects affinities too (gpt-oss
    # router = linear with bias, topk, softmax over the k biased logits)
    router_bias_mode: str = "select"
    # per-expert projection biases (gpt-oss gate_up/down biases)
    expert_bias: bool = False
    # GLU form: "gated" = act(gate)*up; "oss_clamp" = gpt-oss clamped swiglu
    # glu = gate*sigmoid(alpha*gate) with gate<=limit, |up|<=limit,
    # out = (up+1)*glu; "plain" = NO gate: an expert (and the shared expert)
    # is two matrices, act(x W_up) W_down (Nemotron-H's ReLU² experts), and
    # the param tree holds no expert_gate / shared_gate leaf
    glu_style: str = "gated"
    # the width the expert leaves are STORED at where the published
    # ``intermediate_size`` is not whole 128-lane vregs (Nemotron-H's 1856 =
    # 14.5): zero columns of up / rows of down behind it, which add exact
    # zeros to an expert's sum. 0 = as published
    stored_intermediate: int = 0
    glu_alpha: float = 1.702
    glu_limit: float = 7.0
    # group-limited routing (DeepSeek-V3: experts split into n_group groups,
    # only the topk_group best groups — by sum of their top-2 biased scores —
    # are eligible for expert selection)
    n_group: int = 1
    topk_group: int = 1
    # llama4 routing: the routing weight scales the expert INPUT
    # (routed_in = hidden * sigmoid(score); reference llama4 Llama4TextMoe)
    # instead of the expert output — not equivalent through the gated
    # nonlinearity, so it is its own mode
    input_scaled: bool = False
    # TOTAL tokens (B*T) at or below which a step is "few tokens": the walk
    # over the touched experts or, where the kernel declines, the
    # all-experts einsum (whose cost grows with the tokens), never the
    # grouped matmuls. NOT a chunk's boundary: ``takes_ragged`` has the rest.
    # 0 = the grouped matmuls everywhere.
    dense_max_tokens: int = 64
    # hybrid CTE/TKG expert sharding (reference: moe_v2.py:135-161
    # HybridShardingConfig, moe_tkg_ep_degree=1): prefill keeps experts
    # sharded on "ep"; DECODE re-constrains the weights so every device holds
    # ALL experts with the intermediate dim split over ("ep","tp") (the
    # all-gather is loop-invariant, so XLA hoists it out of the decode scan)
    tkg_experts_local: bool = False
    # one chip's share of an expert-parallel layer: the weights hold
    # ``held_experts`` experts (0 = all of them), ``first_expert`` the
    # first; the router's width stays ``num_experts``
    held_experts: int = 0
    first_expert: int = 0
    # the shared expert's output scaled by sigmoid(x . shared_gate_w) per
    # token (HF Qwen2MoeSparseMoeBlock / Qwen3NextSparseMoeBlock)
    shared_gated: bool = False
    # zero-compute experts (LongCat-Flash ``zero_expert_num``, identity): the
    # LAST ``zero_experts`` of the router's ``num_experts`` columns have no
    # matrices; a pick of one adds ``weight x input``. No stack holds them;
    # every share adds their term for its own rows, like a shared expert
    zero_experts: int = 0
    # the router reads the ATTENTION's normed input, not the experts' (the
    # post-attention norm): SmallThinker places its router in front of the
    # attention block, so the routing is known before attention runs. The
    # layer walk hands ``moe_block`` that array as ``router_x``
    router_pre_attn: bool = False
    # added to the sum the top-k affinities are renormalised by (LFM2-MoE:
    # ``w / (sum w + 1e-6)``); 0 = the plain sum
    topk_norm_eps: float = 0.0

    @property
    def num_routed(self) -> int:
        """Router columns that are experts with weights."""
        return self.num_experts - self.zero_experts

    @property
    def num_held(self) -> int:
        """Experts the weights hold: all that have weights, or the share."""
        return self.held_experts or self.num_routed

    @property
    def holds_share(self) -> bool:
        """The router scores columns the stacks do not hold: other chips'
        experts, identity experts, or both."""
        return self.num_held < self.num_experts


# the per-expert leaves of a layer: what the ragged path and the few-token
# kernel read in place
EXPERT_LEAVES = ("expert_gate", "expert_up", "expert_down",
                 "expert_gate_bias", "expert_up_bias", "expert_down_bias")


class LayerOfStack(NamedTuple):
    """One layer's expert leaf, left in its stack: the stacked array
    (L, E, ...) and the layer's index (a Python int or a traced scalar).
    A layer loop puts it in ``layer_w`` in place of the slice for the
    leaves :func:`stack_leaves` names."""

    stack: jnp.ndarray
    layer: Any


def takes_ragged(moe: MoESpec, tokens: int, stack: Any = None) -> bool:
    """The sorted grouped-matmul path serves a step of ``tokens`` (B*T)
    tokens. Decided HERE alone, from what the step shows: its tokens, the
    spec, and the expert leaf ``stack`` (L, E, H, I) its layer loop would
    leave in place (None: the caller cut the layer out, so there is no
    stack to walk).

    At or below ``dense_max_tokens`` the few-token paths serve: the walk
    over the touched experts (``ops/moe_decode.py``), or all experts on all
    tokens in an einsum where the kernel declines. Above it the walk still
    serves every step the kernel takes (``moe_decode.declined``: the
    leaves, and the step's rows whole in VMEM beside the slots, which at a
    hidden size of 2048 is up to 512 tokens: a one-row chunk); a full-batch
    pack, and a chunk over leaves the kernel declines, keep ``ragged_dot``.
    No count of rows an expert separates the two: on a v5e the walk beat
    the grouped matmuls 1.9-2.5 x at every shape timed, 5 to 256 rows an
    expert (PERF.md §6, PR 39: the grouped matmuls spend a 512-row tile on
    every group, the walk a group's own rows). ``dense_max_tokens`` 0 asks
    for the grouped matmuls everywhere."""
    if tokens <= moe.dense_max_tokens:
        return False
    walks = (moe.dense_max_tokens > 0 and stack is not None
             and not moe_decode.declined(moe, stack, tokens))
    return not walks


def sliced_reason(wg) -> str:
    """Why the ragged path cannot read ``wg``'s layer out of its stack in
    place ("" = it can): a quantized leaf is dequantized per call, so it
    is materialised anyway; an expert axis sharded over "ep" cannot be
    merged with the layer axis without a reshard (the merged dimension
    would be block-cyclic)."""
    if is_quantized_leaf(wg):
        return "quantized experts are dequantized per call"
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.shape.get(AXIS_EP, 1) > 1:
        return "expert axis sharded over ep"
    return ""


def stack_leaves(moe: MoESpec, tokens: int, layer_params: Dict[str, Any]
                 ) -> Tuple[str, ...]:
    """The leaves of the stacked ``layer_params`` that a layer loop over a
    step of ``tokens`` tokens leaves in their stack (handing ``moe_block``
    a :class:`LayerOfStack`) instead of slicing a layer out of them: both
    consumers that are custom calls - the grouped matmuls of many tokens,
    the touched-experts kernel of few - read the layer where it lies."""
    wg = layer_params.get("expert_gate", layer_params["expert_up"])
    if (sliced_reason(wg) if takes_ragged(moe, tokens, wg)
            else moe_decode.declined(moe, wg, tokens)):
        return ()
    return tuple(k for k in EXPERT_LEAVES if k in layer_params)


def _act_fn(name: str):
    from ..models.model_base import ACT_FNS
    return ACT_FNS[name]


def route(moe: MoESpec, h: jnp.ndarray, router_w: jnp.ndarray,
          router_bias: Optional[jnp.ndarray] = None
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compute routing: h (B,T,H), router_w (H,E) ->
    (top_vals (B,T,k) fp32 affinities, top_idx (B,T,k) expert ids).

    Reference: RouterTopK (moe_v2.py:5-15) with the affinity knobs of
    MoENeuronConfig (normalize_top_k_affinities, routed_scaling_factor).
    """
    return route_groups(moe, h, router_w, router_bias)[:2]


def chosen_groups(moe: MoESpec, select: jnp.ndarray) -> jnp.ndarray:
    """Group-limited greedy routing (DeepSeek-V3 ``get_topk_indices``): the
    (B,T,G) bool mask of the ``topk_group`` groups, of ``n_group`` groups of
    consecutive experts, with the largest sum of their top-2 biased scores
    ``select`` (B,T,E). A group is in if fewer than ``topk_group`` groups
    rank before it - a larger score, or an equal one at a lower index, the
    order ``top_k`` breaks ties in - which is G x G compares a token and no
    scatter."""
    b, t, e = select.shape
    g = moe.n_group
    top2, _ = jax.lax.top_k(select.reshape(b, t, g, e // g), 2)
    score = top2.sum(axis=-1)                                      # (B,T,G)
    mine, other = score[..., :, None], score[..., None, :]
    earlier = jnp.arange(g)[None, :] < jnp.arange(g)[:, None]
    before = (other > mine) | ((other == mine) & earlier)
    return jnp.sum(before, axis=-1) < moe.topk_group


def route_groups(moe: MoESpec, h: jnp.ndarray, router_w: jnp.ndarray,
                 router_bias: Optional[jnp.ndarray] = None):
    """:func:`route`, and third the (B,T,G) mask of the groups the
    selection was limited to (None: the router has no groups)."""
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (B,T,E)
    if moe.router_act == "sparsemixer":
        return (*_sparsemixer_route(moe, logits), None)
    if router_bias is not None and moe.router_bias_mode == "logits":
        logits = logits + router_bias
        router_bias = None
    if moe.router_act == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif moe.pre_softmax_topk:
        scores = logits
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    select = scores + router_bias if router_bias is not None else scores
    groups = None
    if moe.n_group > 1:
        # the losing groups' scores are zeroed, not dropped
        groups = chosen_groups(moe, select)
        select = jnp.where(
            jnp.repeat(groups, select.shape[-1] // moe.n_group, axis=-1),
            select, 0.0)
    _, top_idx = jax.lax.top_k(select, moe.top_k)                  # (B,T,k)
    top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
    if moe.pre_softmax_topk and moe.router_act != "sigmoid":
        top_vals = jax.nn.softmax(top_vals, axis=-1)
    if moe.normalize_topk:
        top_vals = top_vals / jnp.maximum(
            jnp.sum(top_vals, axis=-1, keepdims=True) + moe.topk_norm_eps,
            1e-20)
    if moe.routed_scaling is not None:
        top_vals = top_vals * moe.routed_scaling
    return top_vals, top_idx, groups


def _sparsemixer_route(moe: MoESpec, logits: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Phi-3.5-MoE sparsemixer routing, inference path (reference:
    contrib/models/Phi-3.5-MoE-instruct — HF modeling_phimoe.sparsemixer
    eval branch): expert i is the argmax of the remaining scores; its
    affinity is a softmax over the scores with entries masked out where
    (max - s) / max(|s|, max) > 2·jitter_eps. top_k must be 2."""
    if moe.top_k != 2:
        raise NotImplementedError(
            f"sparsemixer routing is defined for top_k=2 (got {moe.top_k})")
    eps = moe.sparsemixer_eps

    def pick(scores, ref):
        """One sparsemixer selection over ``scores``. The jitter threshold is
        measured against — and the |.| stabilizer taken from — ``ref``, the
        ORIGINAL logits (HF keeps ``scores.abs()`` across both passes), while
        the max/argmax/softmax all run on ``scores``. Taking both as
        parameters (no closure reads) keeps any call site honest about which
        tensor plays which role."""
        mx = jnp.max(scores, axis=-1, keepdims=True)
        factor = jnp.maximum(jnp.abs(ref), mx)
        masked = jnp.where((mx - ref) / factor > 2 * eps, -jnp.inf, scores)
        idx = jnp.argmax(scores, axis=-1)
        gates = jax.nn.softmax(masked, axis=-1)
        val = jnp.take_along_axis(gates, idx[..., None], axis=-1)
        return val[..., 0], idx

    v1, i1 = pick(logits, logits)
    # second pass: mask out the winner, re-pick over the remainder (threshold
    # vs the REMAINING max, stabilizer still |original logits|)
    masked_scores = jnp.where(
        jax.nn.one_hot(i1, logits.shape[-1], dtype=bool), -jnp.inf, logits)
    v2, i2 = pick(masked_scores, logits)
    return (jnp.stack([v1, v2], axis=-1),
            jnp.stack([i1, i2], axis=-1).astype(jnp.int32))


def combine_matrix(num_experts: int, top_vals: jnp.ndarray,
                   top_idx: jnp.ndarray) -> jnp.ndarray:
    """Scatter (B,T,k) affinities into a dense (B,T,E) combine matrix."""
    b, t, _ = top_vals.shape
    return jnp.zeros((b, t, num_experts), jnp.float32).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
        top_idx].add(top_vals)


def held_combine(moe: MoESpec, top_vals: jnp.ndarray,
                 top_idx: jnp.ndarray) -> jnp.ndarray:
    """The combine matrix over the experts the weights hold, (B,T,held):
    the columns of a share out of the matrix over all the router scored."""
    combine = combine_matrix(moe.num_experts, top_vals, top_idx)
    if moe.holds_share:
        combine = combine[..., moe.first_expert:
                          moe.first_expert + moe.num_held]
    return combine


def share_tally(moe: MoESpec, top_idx: jnp.ndarray,
                live: Optional[jnp.ndarray] = None, read=None) -> jnp.ndarray:
    """Exact counts of one routing over the held experts (all, or a share),
    int32 ``[touched, assigned, read]``: held experts that received at
    least one token, the assignments that fell to held experts, and the
    held experts whose weights the step READ (``read``: the touched list's
    length on the kernel; None = all of them, the dense and ragged paths).
    ``live`` (B,T) bool leaves the rows of a step that carry no sequence
    out of the first two; the kernel reads for every row it is given, so
    ``read >= touched``."""
    held = moe.num_held
    local = top_idx - moe.first_expert
    mine = (local >= 0) & (local < held)
    if live is not None:
        mine = mine & live[..., None]
    hits = jnp.zeros((held,), jnp.int32).at[
        jnp.where(mine, local, held).reshape(-1)].add(1, mode="drop")
    return jnp.stack([jnp.sum(hits > 0), jnp.sum(hits),
                      held if read is None else read]).astype(jnp.int32)


def zero_expert_weight(moe: MoESpec, top_vals: jnp.ndarray,
                       top_idx: jnp.ndarray) -> jnp.ndarray:
    """Per token (B,T) float32, the sum of its picks' weights that fell to
    identity experts (the router's last ``zero_experts`` columns)."""
    return jnp.sum(jnp.where(top_idx >= moe.num_routed, top_vals, 0.0),
                   axis=-1)


def zero_tally(moe: MoESpec, top_idx: jnp.ndarray,
               live: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """int32 ``[picks, identity picks]`` of one routing over the ``live``
    rows: every top-k pick, and those that cost nothing."""
    zero = top_idx >= moe.num_routed
    picks = jnp.ones_like(zero)
    if live is not None:
        zero, picks = zero & live[..., None], picks & live[..., None]
    return jnp.stack([jnp.sum(picks), jnp.sum(zero)]).astype(jnp.int32)


def group_tally(moe: MoESpec, groups: Optional[jnp.ndarray],
                top_idx: jnp.ndarray,
                live: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """int32 ``[rows]``: the ``live`` rows of one routing whose chosen
    groups (``groups`` (B,T,G), :func:`chosen_groups`) include a group that
    holds one of this chip's experts - in the deployment the rows whose
    exchange can reach this chip at all, its fan-in. A router without groups
    (``groups`` None) has one, which every row chose."""
    if groups is None:
        hit = jnp.ones(top_idx.shape[:2], bool)
    else:
        per = moe.num_experts // moe.n_group
        first = moe.first_expert if moe.holds_share else 0
        hit = jnp.any(groups[..., first // per:
                             (first + moe.num_held - 1) // per + 1], axis=-1)
    if live is not None:
        hit = hit & live
    return jnp.sum(hit).astype(jnp.int32).reshape(1)


def _glu(moe: MoESpec, gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    if moe.glu_style == "oss_clamp":
        gate = jnp.minimum(gate, moe.glu_limit)
        up = jnp.clip(up, -moe.glu_limit, moe.glu_limit)
        return (up + 1.0) * (gate * jax.nn.sigmoid(gate * moe.glu_alpha))
    return _act_fn(moe.act)(gate) * up


def experts_dense(moe: MoESpec, x: jnp.ndarray, top_vals: jnp.ndarray,
                  top_idx: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                  wd: jnp.ndarray, bg=None, bu=None, bd=None,
                  local_experts: bool = False) -> jnp.ndarray:
    """All-experts dense compute (reference: moe_token_gen all-experts decode
    kernel). x (B,T,H); wg/wu (E,H,I); wd (E,I,H); b* optional (E,·) biases.

    ``local_experts``: the weights were re-constrained all-experts-local
    with the intermediate dim split tp-major over ("tp","ep")
    (tkg_experts_local decode) — the intermediate activation must follow
    the same layout, or GSPMD reshards the freshly gathered weights
    straight back to expert-parallel (the involuntary-full-remat warning
    MULTICHIP r05 flagged)."""
    dt = x.dtype
    combine = held_combine(moe, top_vals, top_idx)               # (B,T,E)
    if moe.input_scaled:
        # llama4: scale the expert INPUT by the affinity, combine with 1s
        xe = (x[:, :, None, :].astype(jnp.float32)
              * combine[..., None]).astype(dt)          # (B,T,E,H)
        gate = qeinsum("bteh,ehi->btei", xe, wg)
        up = qeinsum("bteh,ehi->btei", xe, wu)
        combine = (combine > 0).astype(jnp.float32)
    else:
        # (B,T,E,I): expert axis sharded on ep, intermediate on tp
        gate = None if wg is None else qeinsum("bth,ehi->btei", x, wg)
        up = qeinsum("bth,ehi->btei", x, wu)
    if bg is not None:
        gate = gate + bg
        up = up + bu
    inter_spec = ((AXIS_DP, None, None, (AXIS_TP, AXIS_EP)) if local_experts
                  else (AXIS_DP, None, AXIS_EP, AXIS_TP))
    inter = shard_constraint(_nonlin(moe, gate, up), *inter_spec)
    outs = qeinsum("btei,eih->bteh", inter, wd)
    if bd is not None:
        outs = outs + bd
    # combine-weighted sum over E — psum over "ep" + "tp" partial sums
    y = jnp.einsum("bteh,bte->bth", outs.astype(jnp.float32), combine)
    return shard_constraint(y.astype(dt), AXIS_DP, None, None)


def sorted_assignments(moe: MoESpec, top_vals: jnp.ndarray,
                       top_idx: jnp.ndarray):
    """A step's B*T*k assignments sorted by held expert: ``order`` (the
    stable sort of the flat assignments; ``order // k`` is the token),
    their ``expert`` (0 .. held - 1, in flat order) and float32 ``weight``,
    and ``group_sizes`` (held,). An assignment to an expert another chip
    holds goes behind every group (expert = held), owns no row of any
    group and weighs nothing."""
    n_e = moe.num_held
    expert = top_idx.reshape(-1)
    weight = top_vals.reshape(-1)
    if moe.holds_share:
        expert = expert - moe.first_expert
        absent = (expert < 0) | (expert >= n_e)
        expert = jnp.where(absent, n_e, expert)
        weight = jnp.where(absent, 0.0, weight)
    order = jnp.argsort(expert)                             # stable
    group_sizes = jnp.bincount(expert, length=n_e).astype(jnp.int32)
    return order, expert, weight, group_sizes


def experts_ragged(moe: MoESpec, x: jnp.ndarray, top_vals: jnp.ndarray,
                   top_idx: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                   wd: jnp.ndarray, bg=None, bu=None, bd=None,
                   layer=None) -> jnp.ndarray:
    """Dropless grouped-matmul path: sort token copies by expert, run
    ``jax.lax.ragged_dot`` per projection, unsort and combine.

    TPU-native analog of the reference's blockwise MoE matmul
    (MoENeuronConfig blockwise configs; SURVEY §2.2). Static shapes: the
    sorted token-copy count is exactly B*T*k.

    ``layer`` None: wg/wu (E,H,I), wd (E,I,H), b* (E,·) are one layer's.
    With ``layer`` (int or traced scalar) they are the STACKED leaves
    (L,E,...): the stack is viewed as L*E groups (a bitcast) of which only
    this layer's E own rows — its group sizes sit at offset ``layer*E`` in
    a zero vector — so the grouped matmul reads the layer's experts where
    they lie. An empty group owns no tile of the kernel's grid; the same
    tiles multiply the same operands as on the layer's slice.
    """
    b, t, h = x.shape
    k = moe.top_k
    dt = x.dtype
    # ragged_dot needs materialized fp expert weights; dequantize per call
    # (prefill is compute-bound, the dequant is amortized over many tokens)
    wg, wu, wd = (dequantize(w, dt) if is_quantized_leaf(w) else w
                  for w in (wg, wu, wd))

    n_e = moe.num_held
    flat_x = x.reshape(b * t, h)
    # an assignment to an expert another chip holds is dropped before the
    # sort: it owns no rows of the grouped matmuls and weighs nothing
    order, flat_expert, flat_weight, group_sizes = sorted_assignments(
        moe, top_vals, top_idx)
    inv = jnp.argsort(order)
    sorted_expert = flat_expert[order]
    sorted_tokens = flat_x[order // k]                      # (N, H)
    if moe.holds_share:
        # rows past the last group are no group's: what the kernel leaves
        # there is not read (bias lookups stay in range)
        present = (sorted_expert < n_e)[:, None]
        sorted_expert = jnp.minimum(sorted_expert, n_e - 1)
    if layer is not None:
        groups = wu.shape[0] * n_e
        wg, wu, wd, bg, bu, bd = (
            None if a is None else a.reshape((groups,) + a.shape[2:])
            for a in (wg, wu, wd, bg, bu, bd))
        first = jnp.asarray(layer, jnp.int32) * n_e
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((groups,), jnp.int32), group_sizes, (first,))
        sorted_expert = sorted_expert + first

    if moe.input_scaled:
        # llama4: affinity scales the expert input; outputs combine with 1s
        sorted_tokens = (sorted_tokens.astype(jnp.float32)
                         * flat_weight[order][:, None]).astype(dt)
        flat_weight = jnp.ones_like(flat_weight)
    gate, up = (None if w is None else jax.lax.ragged_dot(
        sorted_tokens, w, group_sizes) for w in (wg, wu))
    if bg is not None:
        gate = gate + bg[sorted_expert]
        up = up + bu[sorted_expert]
    inter = _nonlin(moe, gate, up)                          # (N, I)
    outs = jax.lax.ragged_dot(inter, wd, group_sizes)       # (N, H)
    if bd is not None:
        outs = outs + bd[sorted_expert]
    if moe.holds_share:
        outs = jnp.where(present, outs, 0)

    outs = outs[inv].astype(jnp.float32) * flat_weight[:, None]
    y = outs.reshape(b * t, k, h).sum(axis=1).reshape(b, t, h)
    return y.astype(dt)


def ragged_row_bytes(moe: MoESpec, t: int, h: int, itemsize: int) -> int:
    """Bytes of temps :func:`experts_ragged` holds for ONE row of ``t``
    tokens: every assignment's token copy and output (``h`` wide, the output
    in float32 too for the combine) and its two float32 intermediates."""
    return t * moe.top_k * (h * (2 * itemsize + 4)
                            + (moe.stored_intermediate
                               or moe.intermediate_size) * (2 * 4 + itemsize))


def experts_ragged_by_rows(moe: MoESpec, x: jnp.ndarray,
                           top_vals: jnp.ndarray, top_idx: jnp.ndarray,
                           *weights, layer=None) -> jnp.ndarray:
    """:func:`experts_ragged`, the step's rows a group at a time where all
    of them at once would outgrow the budget a paged step's attention
    scores are held to (``model_base._score_row_group``): a full-batch pack
    of 32 rows x 256 tokens x top-12 at a hidden size of 6144 is 98,304
    token copies, 2.25 GB of float32 outputs alone. Each group sorts and
    multiplies its own assignments against the same leaves; the sum is the
    same sum."""
    from ..models.model_base import _score_row_group, map_row_groups
    b, t, h = x.shape
    row_bytes = ragged_row_bytes(moe, t, h, x.dtype.itemsize)
    group = _score_row_group(b, row_bytes)
    if group < b:
        kernel_mode.note("moe_ragged", "row-groups", f"{group} of {b} rows")
    return map_row_groups(
        lambda *rows: experts_ragged(moe, *rows, *weights, layer=layer),
        row_bytes, x, top_vals, top_idx)


def experts_touched(moe: MoESpec, x: jnp.ndarray, top_vals: jnp.ndarray,
                    top_idx: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                    wd: jnp.ndarray, layer) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The walk over the touched experts (``ops/moe_decode.py``): x
    (B,T,H); wg (None: plain experts) / wu (L,E,H,I), wd (L,E,I,H) the
    STACKED leaves, ``layer`` the layer's index. At most one tile of rows
    goes whole against every touched expert; a longer step (a one-row chunk)
    hands over its assignments sorted by expert and an expert meets its own
    rows. Returns the output and how many held experts the kernel read."""
    b, t, h = x.shape
    if wg is None:
        return _plain_touched(moe, x, top_vals, top_idx, wu, wd, layer)
    kw = dict(glu=functools.partial(_glu, moe),
              interpret=kernel_mode.pallas_interpret())
    if b * t <= moe_decode.ROW_TILE:
        combine = held_combine(moe, top_vals, top_idx).reshape(b * t, -1)
        y, read = moe_decode.moe_decode_experts(
            x.reshape(b * t, h), combine, wg, wu, wd, layer, **kw)
    else:
        order, _, weight, group_sizes = sorted_assignments(moe, top_vals,
                                                           top_idx)
        y, read = moe_decode.moe_chunk_experts(
            x.reshape(b * t, h), order // moe.top_k, weight[order],
            group_sizes, wg, wu, wd, layer, **kw)
    return y.astype(x.dtype).reshape(b, t, h), read


def moe_block(moe: MoESpec, x: jnp.ndarray, layer_w: Dict[str, Any],
              phase: str = "prefill", tally: Optional[list] = None,
              live: Optional[jnp.ndarray] = None,
              router_x: Optional[jnp.ndarray] = None,
              shared: bool = True) -> jnp.ndarray:
    """Full MoE block: route + experts (+ shared experts; ``shared`` False
    leaves that branch to the caller, :func:`shared_experts`). x (B,T,H).
    ``tally``: a list a layer walk hands in to collect, per expert layer,
    :func:`share_tally` + :func:`zero_tally` + :func:`group_tally` of this
    routing over the ``live`` rows: int32 ``[touched, assigned, read, picks,
    identity picks, rows whose groups reach this chip]``. ``router_x``
    (B,T,H): what the router reads where that is not the experts' input
    (``MoESpec.router_pre_attn``); the routing, the tally and the combine
    weights come from it, the experts multiply ``x``."""
    if moe.router_pre_attn and router_x is None:
        raise ValueError(
            "MoESpec.router_pre_attn: the layer walk must hand moe_block the "
            "attention's normed input as router_x; this walk does not")
    router_bias = layer_w.get("router_bias") if moe.has_router_bias else None
    top_vals, top_idx, groups = route_groups(
        moe, x if router_x is None else router_x, layer_w["router"],
        router_bias)
    if moe.holds_share:
        kernel_mode.note("moe_share", "xla",
                         f"held={moe.num_held} of {moe.num_experts} "
                         f"from {moe.first_expert} top_k={moe.top_k}"
                         + (f" zero={moe.zero_experts}"
                            if moe.zero_experts else "")
                         + (f" groups={moe.n_group} top={moe.topk_group}"
                            if moe.n_group > 1 else "")
                         + (f" shared={moe.shared_mean_of} x "
                            f"{moe.shared_intermediate // moe.shared_mean_of}"
                            " mean" if moe.shared_mean_of else ""))
    y, read = _experts(moe, x, top_vals, top_idx, layer_w, phase)
    if moe.zero_experts:
        # the identity experts' term, the token's own chip's
        y = (y.astype(jnp.float32)
             + zero_expert_weight(moe, top_vals, top_idx)[..., None]
             * x.astype(jnp.float32)).astype(y.dtype)
    if tally is not None:
        tally.append(jnp.concatenate([
            share_tally(moe, top_idx, live, read),
            zero_tally(moe, top_idx, live),
            group_tally(moe, groups, top_idx, live)]))
    if shared and moe.shared_intermediate > 0:
        y = y + shared_experts(moe, x, layer_w)
    return y


def _experts(moe: MoESpec, x: jnp.ndarray, top_vals: jnp.ndarray,
             top_idx: jnp.ndarray, layer_w: Dict[str, Any], phase: str):
    """The routed experts' part of the block by the path the step takes,
    and how many held experts that path read (None = all of them)."""
    biases = ((layer_w["expert_gate_bias"], layer_w["expert_up_bias"],
               layer_w["expert_down_bias"]) if moe.expert_bias
              else (None, None, None))
    wg, wu, wd = (layer_w.get("expert_gate"), layer_w["expert_up"],
                  layer_w["expert_down"])
    tokens = x.shape[0] * x.shape[1]
    ragged = takes_ragged(
        moe, tokens, wu.stack if isinstance(wu, LayerOfStack) else None)
    if isinstance(wu, LayerOfStack):
        # the layer loop decided by the same rules (stack_leaves)
        if ragged:
            kernel_mode.note("moe_ragged", "stacked")
            return experts_ragged_by_rows(
                moe, x, top_vals, top_idx,
                *(None if a is None else a.stack
                  for a in (wg, wu, wd, *biases)), layer=wu.layer), None
        kernel_mode.note(
            "moe_decode", kernel_mode.kernel_path(),
            # (the plan's text; a plain stack names its form and its widths)
            walk_note(moe, wu.stack, tokens))
        return experts_touched(moe, x, top_vals, top_idx, wg and wg.stack,
                               wu.stack, wd.stack, wu.layer)
    if ragged:
        kernel_mode.note("moe_ragged", "sliced",
                         sliced_reason(wu) or "the caller cut the layer out")
        return experts_ragged_by_rows(moe, x, top_vals, top_idx, wg, wu, wd,
                                      *biases), None
    kernel_mode.note("moe_decode", "xla",
                     moe_decode.declined(moe, wu, tokens)
                     or "the caller cut the layer out")
    if (moe.tkg_experts_local and phase == "decode"
            and not is_quantized_leaf(wu)):
        # hybrid TKG sharding: all experts local, intermediate split over
        # BOTH model axes (see MoESpec.tkg_experts_local). DENSE path
        # only: the ragged grouped-matmul fallthrough (decode batch above
        # dense_max_tokens) has no matching intermediate constraint, so
        # re-laid weights would just be resharded back per step — it keeps
        # the stored expert-parallel layout instead.
        #
        # Two-step reshard, tp-MAJOR on the intermediate dim: the sliced
        # layer weight can reach the decode layout by an ep all-gather of
        # the expert dim plus a LOCAL slice of the intermediate shard each
        # device already holds. The previous one-shot constraint (ep-major
        # intermediate split, against a producer whose tp annotation the
        # layer-scan slice had dropped) forced GSPMD into "involuntary
        # full rematerialization" — replicate-then-repartition — on every
        # decode step (MULTICHIP r05 spmd_partitioner warnings). The first
        # constraint re-pins the STORED layout (pure annotation, no data
        # motion); the second is then all-gather + slice.
        def recon(w, stored, target):
            return shard_constraint(shard_constraint(w, *stored), *target)
        wg = recon(wg, (AXIS_EP, None, AXIS_TP),
                   (None, None, (AXIS_TP, AXIS_EP)))
        wu = recon(wu, (AXIS_EP, None, AXIS_TP),
                   (None, None, (AXIS_TP, AXIS_EP)))
        wd = recon(wd, (AXIS_EP, AXIS_TP, None),
                   (None, (AXIS_TP, AXIS_EP), None))
        # the dense compute must KEEP the local-expert layout for its
        # intermediate, or GSPMD reshards the weights back (see
        # experts_dense.local_experts)
        return experts_dense(moe, x, top_vals, top_idx, wg, wu, wd,
                             *biases, local_experts=True), None
    return experts_dense(moe, x, top_vals, top_idx, wg, wu, wd,
                         *biases), None


def shared_experts(moe: MoESpec, x: jnp.ndarray,
                   layer_w: Dict[str, Any]) -> jnp.ndarray:
    """The always-on shared-expert branch (DeepSeek/GLM style), what a block
    adds to its routed sum: one gated MLP of ``shared_intermediate``, behind
    its per-token gate (``shared_gated``) or divided by the number of
    experts it concatenates (``shared_mean_of``: their mean)."""
    s = _nonlin(moe, None if moe.glu_style == "plain"
                else qlinear(x, layer_w["shared_gate"]),
                qlinear(x, layer_w["shared_up"]))
    s = shard_constraint(s, AXIS_DP, None, AXIS_MP)
    s = qlinear(s, layer_w["shared_down"])
    if moe.shared_gated:
        gate = jnp.einsum("bth,h->bt", x, layer_w["shared_gate_w"],
                          preferred_element_type=jnp.float32)
        s = (s.astype(jnp.float32)
             * jax.nn.sigmoid(gate)[..., None]).astype(s.dtype)
    if moe.shared_mean_of:
        s = s / moe.shared_mean_of
    return s


def _nonlin(moe: MoESpec, gate: Optional[jnp.ndarray],
            up: jnp.ndarray) -> jnp.ndarray:
    """An expert's nonlinearity outside the kernels: :func:`_glu`, or a plain
    expert's ``act(up)`` (``gate`` None). (``_glu`` itself is traced INSIDE
    the gated walk's kernel body and keeps its lines: ROADMAP trap 3.)"""
    return _act_fn(moe.act)(up) if gate is None else _glu(moe, gate, up)


def walk_note(moe: MoESpec, stack: jnp.ndarray, tokens: int) -> str:
    """The ``moe_decode`` engagement record of a step of ``tokens`` tokens on
    the walk over ``stack`` (L, E, H, I): the plan's text; a plain stack says
    its form first and, where it is stored wider than published, both widths
    (``plain pieces=3 of 640 (1856 of 1920 stored)``)."""
    note = moe_decode.moe_decode_plan(
        *stack.shape[-2:], stack.dtype,
        moe_decode.matrices_of(moe)).note(tokens)
    if moe.glu_style != "plain":
        return note
    stored = (f" ({moe.intermediate_size} of {stack.shape[-1]} stored)"
              if stack.shape[-1] != moe.intermediate_size else "")
    return f"plain {note}{stored}"


def _plain_touched(moe: MoESpec, x: jnp.ndarray, top_vals: jnp.ndarray,
                   top_idx: jnp.ndarray, wu: jnp.ndarray, wd: jnp.ndarray,
                   layer) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`experts_touched` over PLAIN experts (``glu_style`` "plain"):
    units of two matrices, the activation on the float32 product inside the
    kernel (``moe_decode.moe_decode_units`` / ``moe_chunk_units``)."""
    b, t, h = x.shape
    kw = dict(nonlin=_act_fn(moe.act),
              interpret=kernel_mode.pallas_interpret())
    if b * t <= moe_decode.ROW_TILE:
        combine = held_combine(moe, top_vals, top_idx).reshape(b * t, -1)
        y, read = moe_decode.moe_decode_units(
            x.reshape(b * t, h), combine, (wu, wd), layer, **kw)
    else:
        order, _, weight, group_sizes = sorted_assignments(moe, top_vals,
                                                           top_idx)
        y, read = moe_decode.moe_chunk_units(
            x.reshape(b * t, h), order // moe.top_k, weight[order],
            group_sizes, (wu, wd), layer, **kw)
    return y.astype(x.dtype).reshape(b, t, h), read
