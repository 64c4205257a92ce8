"""The plain reference of ``model_type`` ``deepseek_v3`` (DeepSeek-V3 / R1):
float32, the whole sequence through every layer with a causal mask, no cache,
the attention in its EXPANDED form only (every token's K and V heads
up-projected from its latent and attended as ordinary heads; the program's
decode path attends in the latent space, so the two sides do different
arithmetic). Written from the equations of ISSUE 47, which are
``transformers``' ``modeling_deepseek_v3.py`` (4.57.6) read as mathematics,
and from nothing of this repository's ``modules/``;
``tests/reference_cases/deepseek_v3.json`` holds it to
``DeepseekV3ForCausalLM`` at a toy size.

``N(x; g) = x * rsqrt(mean x^2 + eps) * g``; every projection bias-free;
pre-norm residual blocks ``x += MLA(N(x)); x += FFN(N(x))``; untied head.

* ``MLA(h)``: ``c_q = N(h W_qa; g_qa)``; ``q = c_q W_qb`` as heads of ``[nope
  | rope]``; ``[c_kv | k_r] = h W_kva``; ``c = N(c_kv; g_kva)``; ``[k_nope |
  v] = c W_kvb`` a head; rotary on INTERLEAVED pairs ``(x_2i, x_2i+1)`` of
  q's rope lanes and of ``k_r``, one head shared by all, with yarn's
  frequencies (:func:`yarn_inv_freq`) and cos / sin times ``mscale(factor,
  mscale) / mscale(factor, mscale_all_dim)``; causal ``softmax((q_nope .
  k_nope + q_rope . k_r) (nope + rope)^-0.5 m^2)`` with ``m = 0.1
  mscale_all_dim ln(factor) + 1``, times ``v``, heads concatenated, ``W_o``.
* layers ``0 .. first_k_dense_replace - 1``: ``FFN = SwiGLU`` of
  ``intermediate_size``.
* the others: ``s = sigmoid(h W_g)`` over every routed column, float32; ``sel
  = s + b`` (``e_score_correction_bias``: selection only); a group's score is
  the sum of its top 2 ``sel`` (``n_group`` groups of consecutive experts);
  outside the top ``topk_group`` groups ``sel`` is set to 0; ``idx`` = the top
  ``num_experts_per_tok`` of that; ``w = s[idx] / (sum s[idx] + 1e-20) x
  routed_scaling_factor``; ``FFN = sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)``
  (widths ``moe_intermediate_size`` and that times ``n_shared_experts``). The
  held experts are looped over as the HF module loops.

ONE CHIP'S SHARE. ``n_routed_experts`` is the number of experts the weights
HOLD. Where the config also gives ``router_n_routed_experts`` (the published
count) and ``first_expert``, the router, its bias, the groups and the top k
run over all of them and the sum over the held experts ``first_expert ..
first_expert + n_routed_experts - 1`` only; the shared expert, every chip's
alike, is whole on every share. Without the key every routed expert is held.

Left out: the multi-token-prediction module (``num_nextn_predict_layers``: a
checkpoint's ``model.layers.<num_hidden_layers>``), which the forward pass of
the language model does not run.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import math

import jax
import jax.numpy as jnp

from harness.reference import EXPERT, L, linear, rms_norm, swiglu

ATTN = L + "self_attn."
MLP = L + "mlp."
SHARED = MLP + "shared_experts."

#: faults a comparison against the served path must catch
CONTROLS = ("no_groups", "group_max", "no_select_bias", "bias_in_weights",
            "not_renormalised", "no_routed_scaling", "no_shared", "softmax",
            "no_mscale", "no_yarn", "rope_halves")


def share(cfg):
    """``(routed experts the router scores, held, the first held)``."""
    held = cfg["n_routed_experts"]
    routed = cfg.get("router_n_routed_experts") or held
    first = cfg.get("first_expert") or 0
    if not 0 <= first <= routed - held:
        raise ValueError(f"experts {first}.. of {held} held, {routed} routed")
    return routed, held, first


def selection_bias_width(cfg):
    """Half the width of the seeded ``e_score_correction_bias``: three
    standard deviations of a router logit AT THE SELECTION BOUNDARY, as a
    shift of the sigmoid score there. The logits of a normed input are N(0,
    sigma), sigma = 0.02 sqrt(hidden); the k-th largest of the ``eligible``
    columns (the top groups' experts) sits at the normal quantile z of 1 - k
    / eligible, where a logit shift of d moves the score by d s (1 - s), s =
    sigmoid(z sigma). Smaller, a dropped bias swaps columns of near-equal
    weight, most of them another chip's, and does not show (LongCat-Flash,
    PERF.md section 6, PR 40); much larger it picks alone."""
    from statistics import NormalDist
    routed, _, _ = share(cfg)
    eligible = routed * cfg["topk_group"] // cfg["n_group"]
    sigma = 0.02 * cfg["hidden_size"] ** 0.5
    z = NormalDist().inv_cdf(1.0 - cfg["num_experts_per_tok"] / eligible)
    s = 1.0 / (1.0 + math.exp(-z * sigma))
    return 3.0 * sigma * s * (1.0 - s)


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nh = cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    routed, held, _ = share(cfg)
    dense = list(range(min(cfg.get("first_k_dense_replace", 0), n_l)))
    sparse = list(range(len(dense), n_l))
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        "lm_head.weight": {"shape": (vocab, hid), "init": "normal"},
    }
    for name, shape, init in (
            (L + "input_layernorm.weight", (hid,), "norm"),
            (L + "post_attention_layernorm.weight", (hid,), "norm"),
            (ATTN + "q_a_proj.weight", (rq, hid), "normal"),
            (ATTN + "q_a_layernorm.weight", (rq,), "norm"),
            (ATTN + "q_b_proj.weight", (nh * (nope + rot), rq), "normal"),
            (ATTN + "kv_a_proj_with_mqa.weight", (rkv + rot, hid), "normal"),
            (ATTN + "kv_a_layernorm.weight", (rkv,), "norm"),
            (ATTN + "kv_b_proj.weight", (nh * (nope + dv), rkv), "normal"),
            (ATTN + "o_proj.weight", (hid, nh * dv), "normal")):
        table[name] = {"shape": (n_l,) + shape, "init": init}

    def on(layers, name, shape, init="normal"):
        if layers:
            table[name] = {"shape": (len(layers),) + shape, "init": init,
                           "layers": layers}

    ffn = cfg["intermediate_size"]
    on(dense, MLP + "gate_proj.weight", (ffn, hid))
    on(dense, MLP + "up_proj.weight", (ffn, hid))
    on(dense, MLP + "down_proj.weight", (hid, ffn))
    inter = cfg["moe_intermediate_size"]
    on(sparse, MLP + "gate.weight", (routed, hid))
    # NON-ZERO: a dropped selection bias picks other experts, of other
    # weights
    bias = selection_bias_width(cfg)
    on(sparse, MLP + "gate.e_score_correction_bias", (routed,),
       ["uniform", -bias, bias])
    on(sparse, EXPERT + "gate_proj.weight", (held, inter, hid))
    on(sparse, EXPERT + "up_proj.weight", (held, inter, hid))
    on(sparse, EXPERT + "down_proj.weight", (held, hid, inter))
    wide = inter * cfg.get("n_shared_experts", 0)
    if wide:
        on(sparse, SHARED + "gate_proj.weight", (wide, hid))
        on(sparse, SHARED + "up_proj.weight", (wide, hid))
        on(sparse, SHARED + "down_proj.weight", (hid, wide))
    return table


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg, control=None):
    """``(inverse frequencies (rope / 2,), the factor on cos and sin)`` of
    the rotary head. Without ``rope_scaling`` (or under the control
    ``no_yarn``) the plain ``theta^(-2i/d)``. With yarn a pair's frequency is
    a blend of that (extrapolation) and that over ``factor``
    (interpolation): pair ``i`` keeps ``1 - clip((i - low) / (high - low), 0,
    1)`` of the plain one, ``low`` / ``high`` the pairs that turn
    ``beta_fast`` / ``beta_slow`` times over the original context
    (``d ln(original / (2 pi beta)) / (2 ln theta)``, floored / ceiled)."""
    d = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    plain = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    rs = cfg.get("rope_scaling")
    if not rs or control == "no_yarn":
        return plain, 1.0
    kind = rs.get("type") or rs.get("rope_type")
    if kind != "yarn":
        raise ValueError(f"rope_scaling of type {kind!r}: only yarn is "
                         "written down here")
    factor = float(rs["factor"])
    original = rs.get("original_max_position_embeddings") \
        or cfg["max_position_embeddings"]

    def pair_of(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(rs.get("beta_fast") or 32)), 0)
    high = min(math.ceil(pair_of(rs.get("beta_slow") or 1)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv_freq = plain / factor * ramp + plain * (1.0 - ramp)
    if rs.get("mscale") and rs.get("mscale_all_dim"):
        on_cos = yarn_mscale(factor, rs["mscale"]) \
            / yarn_mscale(factor, rs["mscale_all_dim"])
    else:
        on_cos = yarn_mscale(factor, 1.0)
    return inv_freq, float(on_cos)


def softmax_scale(cfg, control=None):
    """``(nope + rope)^-0.5``, times ``mscale(factor, mscale_all_dim)^2``
    under yarn."""
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling") or {}
    if rs.get("mscale_all_dim") and control != "no_mscale":
        scale *= yarn_mscale(float(rs["factor"]), rs["mscale_all_dim"]) ** 2
    return scale


def rotary(x, positions, inv_freq, on_cos, halves=False):
    """Rotary embedding on pairs ``(x_2i, x_2i+1)`` of the last axis of ``x``
    (B, S, heads, D): HF's ``apply_rotary_pos_emb_interleave`` de-interleaves
    to ``[evens | odds]`` and rotates halves, the same rotation in another
    lane order, which a dot product of two vectors so treated cannot see.
    ``halves`` (a control) pairs ``(x_i, x_{i + D/2})`` instead."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :] * on_cos
    sin = jnp.sin(ang)[None, :, None, :] * on_cos
    if halves:
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
    else:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


#: queries of one block of :func:`attend`: the (heads, block, keys) float32
#: scores of 128 heads over 8192 keys are then 2 GB, not 34
ATTEND_BLOCK = 512


def attend(q_nope, q_rot, k_nope, k_rot, v, scale):
    """Causal softmax attention of expanded heads over the whole sequence:
    q_nope / q_rot (B, S, heads, nope / rope), k_nope / v (B, S, heads, nope
    / v), the one shared rotary head k_rot (B, S, rope). The queries go a
    block at a time (the same arithmetic; a long sequence's scores do not
    fit whole). Returns (B, S, heads, v)."""
    s = q_nope.shape[1]
    k_pos = jnp.arange(s)
    out = []
    for lo in range(0, s, ATTEND_BLOCK):
        rows = slice(lo, min(lo + ATTEND_BLOCK, s))
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, rows], k_nope)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rot[:, rows], k_rot)
                  ) * scale
        causal = k_pos[rows][:, None] >= k_pos[None, :]
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


def mla(cfg, w, i, h, control=None):
    """Latent attention of layer ``i``, expanded: every token's heads are
    materialised from its latent."""
    b, s, _ = h.shape
    nh = cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q = linear(rms_norm(linear(h, w[ATTN + "q_a_proj.weight"][i]),
                        w[ATTN + "q_a_layernorm.weight"][i], eps),
               w[ATTN + "q_b_proj.weight"][i]).reshape(b, s, nh, nope + rot)
    ckv = linear(h, w[ATTN + "kv_a_proj_with_mqa.weight"][i])
    c = rms_norm(ckv[..., :rkv], w[ATTN + "kv_a_layernorm.weight"][i], eps)
    kv = linear(c, w[ATTN + "kv_b_proj.weight"][i]).reshape(b, s, nh,
                                                            nope + dv)
    pos = jnp.arange(s)
    inv_freq, on_cos = yarn_inv_freq(cfg, control)
    halves = control == "rope_halves"
    q_rot = rotary(q[..., nope:], pos, inv_freq, on_cos, halves)
    k_rot = rotary(ckv[:, :, None, rkv:], pos, inv_freq, on_cos,
                   halves)[:, :, 0]
    out = attend(q[..., :nope], q_rot, kv[..., :nope], k_rot, kv[..., nope:],
                 softmax_scale(cfg, control))
    return linear(out.reshape(b, s, nh * dv), w[ATTN + "o_proj.weight"][i])


def routing(cfg, w, j, u, control=None):
    """``(weights (B, S, k), columns (B, S, k), margin (B, S))`` of expert
    layer ``j`` (its row of the stacked router): sigmoid scores of every
    column, the groups ranked by the sum of their top 2 BIASED scores, the
    top k of the biased scores inside the top groups; weights the unbiased
    scores, renormalised, times ``routed_scaling_factor``. The margin is the
    gap between the last masked biased score kept and the first dropped, as
    a share of the last kept."""
    k = cfg["num_experts_per_tok"]
    logits = linear(u, w[MLP + "gate.weight"][j])
    scores = (jax.nn.softmax(logits, axis=-1) if control == "softmax"
              else jax.nn.sigmoid(logits))
    bias = w[MLP + "gate.e_score_correction_bias"][j].astype(jnp.float32)
    choice = scores if control == "no_select_bias" else scores + bias
    groups = cfg.get("n_group") or 1
    if groups > 1 and control != "no_groups":
        by_group = choice.reshape(choice.shape[:-1] + (groups, -1))
        rank = (jnp.max(by_group, axis=-1) if control == "group_max"
                else jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1))
        kept = jax.lax.top_k(rank, cfg["topk_group"])[0][..., -1:]
        choice = jnp.where((rank >= kept)[..., None], by_group,
                           0.0).reshape(choice.shape)
    edge, idx = jax.lax.top_k(choice, k + 1)
    margin = (edge[..., k - 1] - edge[..., k]) / jnp.abs(edge[..., k - 1])
    idx = idx[..., :k]
    top = jnp.take_along_axis(
        scores + bias if control == "bias_in_weights" else scores, idx,
        axis=-1)
    if cfg.get("norm_topk_prob", True) and control != "not_renormalised":
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    if control != "no_routed_scaling":
        top = top * cfg.get("routed_scaling_factor", 1.0)
    return top, idx, margin


def moe(cfg, w, j, u, control=None):
    """``(FFN(u), margin)`` of expert layer ``j``: the held experts' part of
    the routed sum, one expert after another, plus the shared expert."""
    _, held, first = share(cfg)
    top, idx, margin = routing(cfg, w, j, u, control)
    out = jnp.zeros_like(u)
    for e in range(held):
        weight = jnp.sum(jnp.where(idx == first + e, top, 0.0), axis=-1)
        out = out + weight[..., None] * swiglu(
            u, w[EXPERT + "gate_proj.weight"][j, e],
            w[EXPERT + "up_proj.weight"][j, e],
            w[EXPERT + "down_proj.weight"][j, e])
    if SHARED + "gate_proj.weight" in w and control != "no_shared":
        out = out + swiglu(u, w[SHARED + "gate_proj.weight"][j],
                           w[SHARED + "up_proj.weight"][j],
                           w[SHARED + "down_proj.weight"][j])
    return out, margin


def forward(cfg, w, ids, with_margins=False, control=None):
    """Float32 logits ``(B, S, vocab)``; with ``with_margins`` also, per
    position, the least relative gap over its expert layers between the last
    biased router score kept and the first dropped, over ALL the columns
    scored (``inf`` for a stack of dense layers only)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    eps = cfg["rms_norm_eps"]
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    n_dense = min(cfg.get("first_k_dense_replace", 0),
                  cfg["num_hidden_layers"])
    for i in range(cfg["num_hidden_layers"]):
        x = x + mla(cfg, w, i, rms_norm(
            x, w[L + "input_layernorm.weight"][i], eps), control)
        u = rms_norm(x, w[L + "post_attention_layernorm.weight"][i], eps)
        if i < n_dense:
            x = x + swiglu(u, w[MLP + "gate_proj.weight"][i],
                           w[MLP + "up_proj.weight"][i],
                           w[MLP + "down_proj.weight"][i])
        else:
            y, margin = moe(cfg, w, i - n_dense, u, control)
            x, margins = x + y, jnp.minimum(margins, margin)
    logits = linear(rms_norm(x, w["model.norm.weight"], eps),
                    w["lm_head.weight"])
    return (logits, margins) if with_margins else logits
