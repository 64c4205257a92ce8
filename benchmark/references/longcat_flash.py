"""The plain reference of ``model_type`` ``longcat_flash`` (LongCat-Flash-Chat
and the language model of LongCat-Flash-Omni): float32, the whole sequence
through every layer with a causal mask, no cache, the attention in its
EXPANDED form only (every token's K and V heads up-projected from its latent
and attended as ordinary heads; the program's decode path attends in the
latent space, so the two sides do different arithmetic). Written from the
equations of ISSUE 40, which are ``transformers``'
``modeling_longcat_flash.py`` (4.57.6) read as mathematics, and from nothing
of this repository's ``modules/``; ``tests/reference_cases/longcat_flash.json``
holds it to ``LongcatFlashForCausalLM`` at a toy size.

``N(x; g) = x * rsqrt(mean x^2 + eps) * g``; every projection bias-free. A
layer, input ``x``:

    a0 = x + MLA_0(N(x; g_in0));   u = N(a0; g_post0);   s = MoE(u)
    b0 = a0 + MLP_0(u);            a1 = b0 + MLA_1(N(b0; g_in1))
    y  = a1 + MLP_1(N(a1; g_post1)) + s

* ``MLP(h) = W_d(silu(W_g h) * W_u h)``, width ``ffn_hidden_size``.
* ``MLA(h)``: ``q = N(h W_qa; g_qa) W_qb`` as heads of ``[nope | rope]``, ALL
  of it times ``s_q = sqrt(hidden / q_lora_rank)``; ``[c | k_r] = h W_kva``;
  ``c = N(c; g_kva) * s_kv``, ``s_kv = sqrt(hidden / kv_lora_rank)``; ``[k_nope
  | v] = c W_kvb`` a head; rotary (``rope_theta``, INTERLEAVED pairs ``(x_2i,
  x_2i+1)``, no scaling) on q's rope lanes and on ``k_r``, one head shared by
  all; causal ``softmax(q . [k_nope | k_r] / sqrt(nope + rope))`` times ``v``,
  heads concatenated, ``W_o``.
* ``MoE(u)``: ``p = softmax(u W_r)`` over ``router_n_routed_experts +
  zero_expert_num`` columns; the top ``moe_topk`` of ``p + b`` (``b`` =
  ``e_score_correction_bias``: selection only); ``w_i = routed_scaling_factor
  x p_i``, NOT renormalised; ``sum_{i < routed} w_i E_i(u) + (sum_{i >= routed}
  w_i) u``: the last ``zero_expert_num`` columns are identity experts. The
  held experts are looped over as the HF module loops.

ONE CHIP'S SHARE. ``n_routed_experts`` is the number of experts the weights
HOLD. Where the config also gives ``router_n_routed_experts`` (the published
count) and ``first_expert``, the router scores all of them (plus the identity
columns) and the sum runs over the held experts ``first_expert ..
first_expert + n_routed_experts - 1`` only; the identity term, the token's
own chip's, is whole on every share. Without the key every routed expert is
held.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import math
import statistics

import jax
import jax.numpy as jnp

from harness.reference import EXPERT, L, linear, rms_norm, swiglu

ATTN = L + "self_attn.{j}."
MLPS = L + "mlps.{j}."
ROUTER = L + "mlp.router."
IN_NORM = L + "input_layernorm.{j}.weight"
POST_NORM = L + "post_attention_layernorm.{j}.weight"
SUBS = (0, 1)

#: faults a comparison against the served path must catch
CONTROLS = ("no_q_scale", "no_kv_scale", "no_identity", "renormalised",
            "no_routed_scaling", "shortcut_early", "rope_halves",
            "no_select_bias")


def share(cfg):
    """``(routed experts the router scores, held, the first held, identity
    columns)``."""
    held = cfg["n_routed_experts"]
    routed = cfg.get("router_n_routed_experts") or held
    first = cfg.get("first_expert") or 0
    if not 0 <= first <= routed - held:
        raise ValueError(f"experts {first}.. of {held} held, {routed} routed")
    return routed, held, first, cfg.get("zero_expert_num") or 0


def _sub(name, j):
    """A per-sub-block name with its ``{j}`` filled and ``{i}`` left."""
    return name.replace("{j}", str(j))


def weight_shapes(cfg):
    n_l, hid, vocab = cfg["num_layers"], cfg["hidden_size"], cfg["vocab_size"]
    nh = cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ffn, inter = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    routed, held, _, zero = share(cfg)
    width = routed + zero
    # a selection bias worth up to three standard deviations of a router
    # logit AT THE SELECTION BOUNDARY: the logits of a normed input
    # are N(0, sigma), sigma = 0.02 sqrt(hidden), so the moe_topk-th largest
    # probability is p_k = exp(z sigma) / (width exp(sigma^2 / 2)) with z the
    # normal quantile of 1 - moe_topk / width, and a bias b moves a column
    # at the boundary as a logit shift of b / p_k would. Smaller it swaps
    # columns of near-equal weight, most of them another chip's, and a
    # dropped bias does not show (a probability's spacing, then 1.5 sigma,
    # were tried: PERF.md section 6, PR 40); much larger it picks alone
    sigma = 0.02 * hid ** 0.5
    z = statistics.NormalDist().inv_cdf(1.0 - cfg["moe_topk"] / width)
    bias = 3.0 * sigma * math.exp(z * sigma - sigma ** 2 / 2) / width
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        "lm_head.weight": {"shape": (vocab, hid), "init": "normal"},
        ROUTER + "classifier.weight": {"shape": (n_l, width, hid),
                                       "init": "normal"},
        # NON-ZERO: a dropped selection bias picks other experts, of other
        # weights
        ROUTER + "e_score_correction_bias": {
            "shape": (n_l, width), "init": ["uniform", -bias, bias]},
        EXPERT + "gate_proj.weight": {"shape": (n_l, held, inter, hid),
                                      "init": "normal"},
        EXPERT + "up_proj.weight": {"shape": (n_l, held, inter, hid),
                                    "init": "normal"},
        EXPERT + "down_proj.weight": {"shape": (n_l, held, hid, inter),
                                      "init": "normal"},
    }
    for j in SUBS:
        for name, shape, init in (
                (IN_NORM, (hid,), "norm"), (POST_NORM, (hid,), "norm"),
                (ATTN + "q_a_proj.weight", (rq, hid), "normal"),
                (ATTN + "q_a_layernorm.weight", (rq,), "norm"),
                (ATTN + "q_b_proj.weight", (nh * (nope + rot), rq), "normal"),
                (ATTN + "kv_a_proj_with_mqa.weight", (rkv + rot, hid),
                 "normal"),
                (ATTN + "kv_a_layernorm.weight", (rkv,), "norm"),
                (ATTN + "kv_b_proj.weight", (nh * (nope + dv), rkv),
                 "normal"),
                (ATTN + "o_proj.weight", (hid, nh * dv), "normal"),
                (MLPS + "gate_proj.weight", (ffn, hid), "normal"),
                (MLPS + "up_proj.weight", (ffn, hid), "normal"),
                (MLPS + "down_proj.weight", (hid, ffn), "normal")):
            table[_sub(name, j)] = {"shape": (n_l,) + shape, "init": init}
    return table


def rotary_interleaved(x, positions, theta, halves=False):
    """Rotary embedding on pairs ``(x_2i, x_2i+1)`` of the last axis of ``x``
    (B, S, heads, D): HF's ``apply_rotary_pos_emb_interleave`` de-interleaves
    to ``[evens | odds]`` and rotates halves, the same rotation in another
    lane order, which a dot product of two vectors so treated cannot see.
    ``halves`` (a control) pairs ``(x_i, x_{i + D/2})`` instead."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if halves:
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
    else:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attend(q_nope, q_rot, k_nope, k_rot, v, q_pos, k_pos):
    """Causal softmax attention of expanded heads: q_nope / q_rot (B, Q,
    heads, nope / rope) at positions ``q_pos`` over k_nope / v (B, K, heads,
    nope / v) and the one shared rotary head k_rot (B, K, rope) at ``k_pos``.
    Returns (B, Q, heads, v)."""
    d = q_nope.shape[-1] + q_rot.shape[-1]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_rot, k_rot)) * d ** -0.5
    causal = q_pos[:, None] >= k_pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def mla(cfg, w, i, j, h, control=None):
    """Latent attention ``j`` of layer ``i``, expanded: every token's heads
    are materialised from its latent."""
    b, s, hid = h.shape
    nh = cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    s_q = (hid / cfg["q_lora_rank"]) ** 0.5 \
        if cfg.get("mla_scale_q_lora", True) and control != "no_q_scale" \
        else 1.0
    s_kv = (hid / cfg["kv_lora_rank"]) ** 0.5 \
        if cfg.get("mla_scale_kv_lora", True) and control != "no_kv_scale" \
        else 1.0
    a = _sub(ATTN, j)
    q = linear(rms_norm(linear(h, w[a + "q_a_proj.weight"][i]),
                        w[a + "q_a_layernorm.weight"][i], eps),
               w[a + "q_b_proj.weight"][i]).reshape(b, s, nh, nope + rot) * s_q
    ckv = linear(h, w[a + "kv_a_proj_with_mqa.weight"][i])
    c = rms_norm(ckv[..., :cfg["kv_lora_rank"]],
                 w[a + "kv_a_layernorm.weight"][i], eps) * s_kv
    kv = linear(c, w[a + "kv_b_proj.weight"][i]).reshape(b, s, nh, nope + dv)
    pos = jnp.arange(s)
    theta = float(cfg["rope_theta"])
    halves = control == "rope_halves"
    q_rot = rotary_interleaved(q[..., nope:], pos, theta, halves)
    k_rot = rotary_interleaved(ckv[:, :, None, cfg["kv_lora_rank"]:], pos,
                               theta, halves)[:, :, 0]
    out = attend(q[..., :nope], q_rot, kv[..., :nope], k_rot, kv[..., nope:],
                 pos, pos)
    return linear(out.reshape(b, s, nh * dv), w[a + "o_proj.weight"][i])


def routing(cfg, w, i, u, control=None):
    """``(weights (B, S, k), columns (B, S, k), margin (B, S))`` of layer
    ``i``: softmax over every column the router scores, the top ``moe_topk``
    of the BIASED scores, weights the unbiased probabilities times
    ``routed_scaling_factor``. The margin is the gap between the last biased
    score kept and the first dropped, as a share of the last kept."""
    k = cfg["moe_topk"]
    probs = jax.nn.softmax(linear(u, w[ROUTER + "classifier.weight"][i]),
                           axis=-1)
    choice = probs if control == "no_select_bias" else probs + w[
        ROUTER + "e_score_correction_bias"][i].astype(jnp.float32)
    edge, idx = jax.lax.top_k(choice, k + 1)
    margin = (edge[..., k - 1] - edge[..., k]) / jnp.abs(edge[..., k - 1])
    idx = idx[..., :k]
    top = jnp.take_along_axis(probs, idx, axis=-1)
    if control == "renormalised":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    if control != "no_routed_scaling":
        top = top * cfg.get("routed_scaling_factor", 1.0)
    return top, idx, margin


def moe(cfg, w, i, u, control=None):
    """``(s, margin)``: the held experts' part of the routed sum, one expert
    after another, plus the identity experts' term."""
    routed, held, first, _ = share(cfg)
    top, idx, margin = routing(cfg, w, i, u, control)
    out = jnp.zeros_like(u)
    for e in range(held):
        weight = jnp.sum(jnp.where(idx == first + e, top, 0.0), axis=-1)
        out = out + weight[..., None] * swiglu(
            u, w[EXPERT + "gate_proj.weight"][i, e],
            w[EXPERT + "up_proj.weight"][i, e],
            w[EXPERT + "down_proj.weight"][i, e])
    if control != "no_identity":
        out = out + jnp.sum(jnp.where(idx >= routed, top, 0.0),
                            axis=-1)[..., None] * u
    return out, margin


def layer(cfg, w, i, x, control=None):
    """One layer: ``(y, margin)``."""
    eps = cfg["rms_norm_eps"]
    shortcut = margin = None
    for j in SUBS:
        x = x + mla(cfg, w, i, j,
                    rms_norm(x, w[_sub(IN_NORM, j)][i], eps), control)
        u = rms_norm(x, w[_sub(POST_NORM, j)][i], eps)
        if j == 0:
            shortcut, margin = moe(cfg, w, i, u, control)
        m = _sub(MLPS, j)
        x = x + swiglu(u, w[m + "gate_proj.weight"][i],
                       w[m + "up_proj.weight"][i],
                       w[m + "down_proj.weight"][i])
        if j == 0 and control == "shortcut_early":
            x, shortcut = x + shortcut, 0.0
    return x + shortcut, margin


def forward(cfg, w, ids, with_margins=False, control=None):
    """Float32 logits ``(B, S, vocab)``; with ``with_margins`` also, per
    position, the least relative gap over its layers between the last biased
    router score kept and the first dropped, over ALL the columns scored."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(cfg["num_layers"]):
        x, margin = layer(cfg, w, i, x, control)
        margins = jnp.minimum(margins, margin)
    logits = linear(rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"]),
                    w["lm_head.weight"])
    return (logits, margins) if with_margins else logits
