"""The plain reference of ``model_type`` ``ling_kda`` (the language model of
inclusionAI's Ling-3.0-flash / Ling-3.0-flash-VL): float32, the whole
sequence through every layer, the linear layers ONE TOKEN AFTER ANOTHER
through the state recurrence (no chunked form, no kernel, no cache), the
latent attention in its EXPANDED form only. Written from the equations of
ISSUE 67 - Kimi Delta Attention as published (arXiv:2510.26692) under the
config's ``kda_*`` keys, DeepSeek-V2-Lite's form of MLA, DeepSeek-V3's
group-limited router - and from nothing of this repository's ``modules/``.
This machine has neither ``fla`` nor a Ling model in ``transformers``; what
was taken by convention is listed under ``assumed`` in
``configs/ling-3.0-flash.json``. ``tests/test_reference_ling_kda.py`` holds
the MLA block and the router to ``transformers``' DeepSeek modules and the
recurrence to a second form.

``N(x; g) = x * rsqrt(mean x^2 + eps) * g``; no bias anywhere; ``H`` heads of
``head_dim`` in both temporal blocks. Every layer ``l``: ``h += T_l(N(h));
h += F_l(N(h))``; ``logits = N(h_L) W_head`` (untied).

* ``T_l``, where ``(l + 1) % layer_group_size != 0``: KDA. ``[q; k; v] =
  silu(conv(W_{q,k,v} u))``, a depthwise causal convolution of
  ``short_conv_kernel_size`` each; per head ``q <- q / |q| * d^-0.5``, ``k <-
  k / |k|`` (eps 1e-6 inside the root); ``g = kda_lower_bound * sigmoid(
  exp(A_log)[head] * (W_f u + dt_bias))`` BY CHANNEL (``W_f`` hidden -> H x
  d, full rank), ``alpha = exp(g)``; ``beta = sigmoid(W_b u)`` a head;
  ``S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t`` with ``S`` of ``(d, d)`` a head; ``y = N(o; w_o) *
  sigmoid(W_g u)[head]`` (the norm over a head's ``d`` with one weight
  shared by the heads, THEN one gate a head); ``W_o y``.
* ``T_l`` otherwise: MLA. ``q = W_q u`` as heads of ``[nope | rope]``; ``[c
  | k_r] = W_dkv u``; ``c <- N(c)``; ``[k_nope | v] = W_ukv c`` a head; rotary
  in the HALF-SPLIT layout (pairs ``(x_i, x_{i + D/2})``) on q's rope lanes
  and on ``k_r``, one head shared by all; causal ``softmax((q_nope . k_nope
  + q_rope . k_r) (nope + rope)^-0.5)`` times ``v``; each head's output times
  ``sigmoid(W_g u)[head]``; ``W_o``.
* ``F_l``: a SwiGLU of ``intermediate_size`` for ``l <
  first_k_dense_replace``; else ``s = sigmoid(W_r u)`` over every routed
  column, float32; ``sel = s + b``; a group's score is the sum of its top 2
  ``sel`` (``n_group`` groups of consecutive experts); outside the top
  ``topk_group`` groups ``sel`` is 0; the top ``num_experts_per_tok`` of
  that; ``w = s[idx] / (sum s[idx] + 1e-20) x routed_scaling_factor``; ``sum_e
  w_e SwiGLU_e(u) + SwiGLU_shared(u)``, no clamp (the published limit lists
  are 0 on the layers this is run on; a non-zero entry is refused).

ONE CHIP'S SHARE. ``num_experts`` is the number of experts the weights HOLD.
Where the config also gives ``router_num_experts`` (the published count) and
``first_expert``, the router, its bias, the groups and the top k run over all
of them and the sum over the held experts ``first_expert .. first_expert +
num_experts - 1`` only; the shared expert is whole on every share.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import math

import jax
import jax.numpy as jnp

from harness.reference import EXPERT, L, linear, rms_norm, swiglu

ATTN = L + "self_attn."
LIN = L + "linear_attn."
MLP = L + "mlp."
SHARED = MLP + "shared_experts."
L2_EPS = 1e-6

#: faults a comparison against the served path must catch
CONTROLS = (
    # the linear layers
    "decay_by_head", "no_bound", "no_dt_bias", "decay_after_write",
    "no_beta", "no_qk_norm", "no_conv_silu", "no_conv_tail",
    "no_head_gate_kda", "gate_before_norm", "bf16_state",
    # latent attention
    "no_head_gate_mla", "no_rotary", "rope_interleaved", "no_latent_norm",
    # the router
    "softmax", "no_groups", "no_select_bias", "bias_in_weights",
    "not_renormalised", "no_routed_scaling", "no_shared")

#: tokens after which the control ``no_conv_tail`` forgets a convolution's
#: history (a served path that drops the tail between steps forgets it at
#: every step; this forgets it often enough to show anywhere)
TAIL_DROP_EVERY = 16


def share(cfg):
    """``(routed experts the router scores, held, the first held)``."""
    held = cfg["num_experts"]
    routed = cfg.get("router_num_experts") or held
    first = cfg.get("first_expert") or 0
    if not 0 <= first <= routed - held:
        raise ValueError(f"experts {first}.. of {held} held, {routed} routed")
    return routed, held, first


def linear_layers(cfg):
    """``(KDA layers, MLA layers)``: layer ``l`` is MLA where ``(l + 1) %
    layer_group_size == 0``."""
    period = cfg["layer_group_size"]
    every = range(cfg["num_hidden_layers"])
    return ([i for i in every if (i + 1) % period],
            [i for i in every if (i + 1) % period == 0])


def check_defined(cfg):
    """Refuse what the published keys give no equation for."""
    n = cfg["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = cfg.get(key) or []
        if limits and (len(limits) != n or any(limits)):
            raise ValueError(f"{key} must name {n} layers, all 0 (no clamp): "
                             f"{limits}")
    for key, want in (("use_nGPT", False), ("value_norm", False),
                      ("up_proj_norm", False), ("scale_router_input", False),
                      ("use_kda_lora", False), ("q_lora_rank", None),
                      ("use_mla_nope", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: only {want!r} is "
                             "written down here")


def selection_bias_width(cfg):
    """Half the width of the seeded selection bias: three standard deviations
    of a router logit at the selection boundary, as a shift of the sigmoid
    score there (``references/deepseek_v3.py`` has the argument)."""
    from statistics import NormalDist
    routed, _, _ = share(cfg)
    eligible = routed * cfg["topk_group"] // cfg["n_group"]
    sigma = 0.02 * cfg["hidden_size"] ** 0.5
    z = NormalDist().inv_cdf(1.0 - cfg["num_experts_per_tok"] / eligible)
    s = 1.0 / (1.0 + math.exp(-z * sigma))
    return 3.0 * sigma * s * (1.0 - s)


def weight_shapes(cfg):
    check_defined(cfg)
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    rkv = cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    k = cfg["short_conv_kernel_size"]
    routed, held, _ = share(cfg)
    lin, full = linear_layers(cfg)
    dense = list(range(min(cfg.get("first_k_dense_replace", 0), n_l)))
    sparse = list(range(len(dense), n_l))
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        "lm_head.weight": {"shape": (vocab, hid), "init": "normal"},
        L + "input_layernorm.weight": {"shape": (n_l, hid), "init": "norm"},
        L + "post_attention_layernorm.weight": {"shape": (n_l, hid),
                                                "init": "norm"},
    }

    def on(layers, name, shape, init="normal"):
        if layers:
            table[name] = {"shape": (len(layers),) + shape, "init": init,
                           "layers": layers}

    # the convolutions as nn.Conv1d draws them (uniform within 1 / sqrt(K)).
    # The decay g = -5 sigmoid(rate (W_f u + dt_bias)): rate = exp(A_log) in
    # 1..2 a head, dt_bias in -5..5 BY CHANNEL, W_f u ~ N(0, 1): the
    # channels of one head spread over all of (-5, 0), some hold a state for
    # a thousand tokens and some forget it in one (g near -5: where a chunked
    # form that factors the decays carelessly overflows), and a decay taken
    # by head (their mean) is another model
    bound = float(k) ** -0.5
    for name, shape, init in (
            ("q_proj.weight", (nh * d, hid), "normal"),
            ("k_proj.weight", (nh * d, hid), "normal"),
            ("v_proj.weight", (nh * d, hid), "normal"),
            ("f_proj.weight", (nh * d, hid), "normal"),
            ("b_proj.weight", (nh, hid), "normal"),
            ("g_proj.weight", (nh, hid), "normal"),
            ("o_proj.weight", (hid, nh * d), "normal"),
            ("q_conv1d.weight", (nh * d, 1, k), ["uniform", -bound, bound]),
            ("k_conv1d.weight", (nh * d, 1, k), ["uniform", -bound, bound]),
            ("v_conv1d.weight", (nh * d, 1, k), ["uniform", -bound, bound]),
            ("A_log", (nh,), ["uniform", 0.0, 0.7]),
            ("dt_bias", (nh * d,), ["uniform", -5.0, 5.0]),
            ("o_norm.weight", (d,), "norm")):
        on(lin, LIN + name, shape, init)
    for name, shape, init in (
            ("q_proj.weight", (nh * (nope + rot), hid), "normal"),
            ("kv_a_proj_with_mqa.weight", (rkv + rot, hid), "normal"),
            ("kv_a_layernorm.weight", (rkv,), "norm"),
            ("kv_b_proj.weight", (nh * (nope + dv), rkv), "normal"),
            ("g_proj.weight", (nh, hid), "normal"),
            ("o_proj.weight", (hid, nh * dv), "normal")):
        on(full, ATTN + name, shape, init)
    ffn = cfg["intermediate_size"]
    on(dense, MLP + "gate_proj.weight", (ffn, hid))
    on(dense, MLP + "up_proj.weight", (ffn, hid))
    on(dense, MLP + "down_proj.weight", (hid, ffn))
    inter = cfg["moe_intermediate_size"]
    on(sparse, MLP + "gate.weight", (routed, hid))
    bias = selection_bias_width(cfg)
    on(sparse, MLP + "gate.e_score_correction_bias", (routed,),
       ["uniform", -bias, bias])
    on(sparse, EXPERT + "gate_proj.weight", (held, inter, hid))
    on(sparse, EXPERT + "up_proj.weight", (held, inter, hid))
    on(sparse, EXPERT + "down_proj.weight", (held, hid, inter))
    wide = cfg.get("moe_shared_expert_intermediate_size", inter) \
        * cfg.get("num_shared_experts", 1)
    if wide:
        on(sparse, SHARED + "gate_proj.weight", (wide, hid))
        on(sparse, SHARED + "up_proj.weight", (wide, hid))
        on(sparse, SHARED + "down_proj.weight", (hid, wide))
    return table


# ---------------------------------------------------------------------------
# the linear layers
# ---------------------------------------------------------------------------

def _causal_conv(x, weight, control=None):
    """Depthwise causal convolution of ``x`` (B, S, C) with the published
    ``Conv1d.weight`` (C, 1, K): ``out_t = sum_i w[:, i] x_{t - (K-1) + i}``,
    zeros before the sequence."""
    k, s = weight.shape[-1], x.shape[1]
    taps = weight.astype(jnp.float32)[:, 0, :]                    # (C, K)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    t = jnp.arange(s)
    out = 0.0
    for i in range(k):
        term = padded[:, i:i + s] * taps[:, i]
        if control == "no_conv_tail":
            # the input (K - 1 - i) tokens back lies before the last drop
            term = jnp.where((t % TAIL_DROP_EVERY >= k - 1 - i)[None, :, None],
                             term, 0.0)
        out = out + term
    return out


def _l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_inputs(cfg, w, j, u, control=None):
    """``(q, k, v, g, beta, gate)`` of linear layer ``j`` (its index among
    the linear layers) over ``u`` (B, S, hidden): q, k ``(B, S, H, d)``
    normalised (q scaled), v ``(B, S, H, d)``, the log decay g ``(B, S, H,
    d)`` by channel, beta and gate ``(B, S, H)`` after their sigmoids."""
    b, s, _ = u.shape
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    f32 = jnp.float32

    def conv_branch(name):
        x = _causal_conv(linear(u, w[LIN + name + "_proj.weight"][j]),
                         w[LIN + name + "_conv1d.weight"][j], control)
        if control != "no_conv_silu":
            x = jax.nn.silu(x)
        return x.reshape(b, s, nh, d)
    q, k, v = conv_branch("q"), conv_branch("k"), conv_branch("v")
    if control != "no_qk_norm":
        q, k = _l2_normalise(q), _l2_normalise(k)
    q = q * d ** -0.5
    rate = jnp.exp(w[LIN + "A_log"][j].astype(f32))[:, None]     # (H, 1)
    pre = linear(u, w[LIN + "f_proj.weight"][j])
    if control != "no_dt_bias":
        pre = pre + w[LIN + "dt_bias"][j].astype(f32)
    pre = pre.reshape(b, s, nh, d)
    if control == "no_bound":
        g = -rate * jax.nn.softplus(pre)
    else:
        g = cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * pre)
    if control == "decay_by_head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(linear(u, w[LIN + "b_proj.weight"][j]))
    if control == "no_beta":
        beta = jnp.ones_like(beta)
    gate = jax.nn.sigmoid(linear(u, w[LIN + "g_proj.weight"][j]))
    return q, k, v, g, beta, gate


def kda_rule(q, k, v, g, beta, state=None, control=None):
    """The delta rule gated by channel, token by token: ``(o (B, S, H, d_v),
    S_last (B, H, d_k, d_v))`` from ``state`` (zeros where None)."""
    b, _, nh, dk = k.shape
    dv = v.shape[-1]
    late = control == "decay_after_write"

    def step(st, t):
        q_t, k_t, v_t, g_t, b_t = t
        alpha = jnp.exp(g_t)[..., None]                     # (B, H, dk, 1)
        if not late:
            st = st * alpha
        read = jnp.einsum("bhkv,bhk->bhv", st, k_t)
        st = st + (k_t[..., :, None]
                   * (b_t[..., None] * (v_t - read))[..., None, :])
        if late:
            st = st * alpha
        if control == "bf16_state":
            st = st.astype(jnp.bfloat16).astype(jnp.float32)
        return st, jnp.einsum("bhkv,bhk->bhv", st, q_t)

    def time_first(t):
        return jnp.moveaxis(t, 1, 0)
    if state is None:
        state = jnp.zeros((b, nh, dk, dv), jnp.float32)
    last, o = jax.lax.scan(step, state, tuple(
        time_first(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def kda(cfg, w, j, u, control=None):
    """``(W_o y (B, S, hidden), the state after the last token)``."""
    b, s, _ = u.shape
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    q, k, v, g, beta, gate = kda_inputs(cfg, w, j, u, control)
    o, last = kda_rule(q, k, v, g, beta, control=control)
    norm_w, eps = w[LIN + "o_norm.weight"][j], cfg["rms_norm_eps"]
    if control == "gate_before_norm":
        y = rms_norm(o * gate[..., None], norm_w, eps)
    else:
        y = rms_norm(o, norm_w, eps)
        if control != "no_head_gate_kda":
            y = y * gate[..., None]
    return linear(y.reshape(b, s, nh * d), w[LIN + "o_proj.weight"][j]), last


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def rotary(x, positions, theta, interleaved=False):
    """Rotary embedding on the last axis of ``x`` (B, S, heads, D), pairs
    ``(x_i, x_{i + D/2})`` (the half-split layout); ``interleaved`` (a
    control) pairs ``(x_2i, x_2i+1)`` instead."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


#: queries of one block of :func:`attend` (a long sequence's scores do not
#: fit whole; ``scripts/gate56.py``'s blocked walk sets it smaller)
ATTEND_BLOCK = 512


def attend(q_nope, q_rot, k_nope, k_rot, v, scale):
    """Causal softmax attention of expanded heads over the whole sequence:
    q_nope / q_rot (B, S, heads, nope / rope), k_nope / v (B, S, heads, nope
    / v), the one shared rotary head k_rot (B, S, rope), the queries a block
    at a time (the same arithmetic). Returns (B, S, heads, v)."""
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


def mla(cfg, w, j, u, control=None):
    """Latent attention of MLA layer ``j`` (its index among them), expanded:
    every token's heads are materialised from its latent."""
    b, s, _ = u.shape
    nh = cfg["num_attention_heads"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q = linear(u, w[ATTN + "q_proj.weight"][j]).reshape(b, s, nh, nope + rot)
    ckv = linear(u, w[ATTN + "kv_a_proj_with_mqa.weight"][j])
    c = ckv[..., :rkv]
    if control != "no_latent_norm":
        c = rms_norm(c, w[ATTN + "kv_a_layernorm.weight"][j], eps)
    kv = linear(c, w[ATTN + "kv_b_proj.weight"][j]).reshape(b, s, nh,
                                                            nope + dv)
    q_rot, k_rot = q[..., nope:], ckv[:, :, None, rkv:]
    if control != "no_rotary":
        pos, theta = jnp.arange(s), float(cfg["rope_theta"])
        q_rot = rotary(q_rot, pos, theta, control == "rope_interleaved")
        k_rot = rotary(k_rot, pos, theta, control == "rope_interleaved")
    out = attend(q[..., :nope], q_rot, kv[..., :nope], k_rot[:, :, 0],
                 kv[..., nope:], (nope + rot) ** -0.5)
    if control != "no_head_gate_mla":
        out = out * jax.nn.sigmoid(
            linear(u, w[ATTN + "g_proj.weight"][j]))[..., None]
    return linear(out.reshape(b, s, nh * dv), w[ATTN + "o_proj.weight"][j])


# ---------------------------------------------------------------------------
# the experts
# ---------------------------------------------------------------------------

def routing(cfg, w, j, u, control=None):
    """``(weights (B, S, k), columns (B, S, k), margin (B, S))`` of expert
    layer ``j`` (its row of the stacked router): DeepSeek-V3's group-limited
    router. The margin is the gap between the last masked biased score kept
    (the k-th) and the first dropped (the k + 1-th), inside the chosen
    groups, as a share of the last kept."""
    k = cfg["num_experts_per_tok"]
    logits = linear(u, w[MLP + "gate.weight"][j])
    scores = (jax.nn.softmax(logits, axis=-1) if control == "softmax"
              else jax.nn.sigmoid(logits))
    bias = w[MLP + "gate.e_score_correction_bias"][j].astype(jnp.float32)
    choice = scores if control == "no_select_bias" else scores + bias
    groups = cfg.get("n_group") or 1
    if groups > 1 and control != "no_groups":
        by_group = choice.reshape(choice.shape[:-1] + (groups, -1))
        rank = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
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
    """``(F(u), margin)`` of expert layer ``j``: the held experts' part of
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


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------

def layer(cfg, w, i, x, control=None):
    """Layer ``i`` over ``x`` (B, S, H): ``(x', margin (B, S), state)``;
    ``margin`` is ``inf`` unless the layer routes, ``state`` the linear
    layer's ``S`` after the last token or None."""
    eps = cfg["rms_norm_eps"]
    lin, full = linear_layers(cfg)
    n_dense = min(cfg.get("first_k_dense_replace", 0),
                  cfg["num_hidden_layers"])
    u = rms_norm(x, w[L + "input_layernorm.weight"][i], eps)
    state = None
    if i in full:
        x = x + mla(cfg, w, full.index(i), u, control)
    else:
        mixed, state = kda(cfg, w, lin.index(i), u, control)
        x = x + mixed
    u = rms_norm(x, w[L + "post_attention_layernorm.weight"][i], eps)
    margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    if i < n_dense:
        x = x + swiglu(u, w[MLP + "gate_proj.weight"][i],
                       w[MLP + "up_proj.weight"][i],
                       w[MLP + "down_proj.weight"][i])
    else:
        y, margin = moe(cfg, w, i - n_dense, u, control)
        x = x + y
    return x, margin, state


def final_hidden(cfg, w, ids, control=None):
    """``(N(h_L) (B, S, H), margins (B, S), states)``: what the head reads,
    per position the smallest routing margin over its expert layers, and
    every linear layer's state after the last token."""
    check_defined(cfg)
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    states = []
    for i in range(cfg["num_hidden_layers"]):
        x, margin, state = layer(cfg, w, i, x, control)
        margins = jnp.minimum(margins, margin)
        if state is not None:
            states.append(state)
    return (rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"]),
            margins, states)


def forward(cfg, w, ids, with_margins=False, control=None):
    """Float32 logits ``(B, S, vocab)``; with ``with_margins`` also ``(B, S)``
    float32, the smallest routing margin over a position's expert layers."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    x, margins, _ = final_hidden(cfg, w, ids, control)
    logits = linear(x, w["lm_head.weight"])
    return (logits, margins) if with_margins else logits


def final_states(cfg, w, ids):
    """Every linear layer's state after the last token of ``ids``, ``(linear
    layers, B, H, d_k, d_v)`` float32: what a served sequence's state slot is
    held to (the logits of a short run cannot tell the precision the state is
    carried in; the state can)."""
    return jnp.stack(final_hidden(cfg, w, ids)[2])
