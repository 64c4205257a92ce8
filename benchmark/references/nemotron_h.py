"""The plain reference of ``model_type`` ``nemotron_h``
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): float32, the whole sequence
through every layer, no cache, no kernels, no chunking; the Mamba-2 state one
token after another through the SEQUENTIAL recurrence (not the one-pass SSD
form). Written from the equations of ISSUE 64 (the catalog row's config and
``described_as``, the Nemotron-H modelling file as published, and the Mamba-2
paper's recurrence) and from nothing of this repository's ``modules/``;
``tests/test_reference_nemotron_h.py`` holds the mixer to transformers' Bamba
Mamba-2 layer at 8 groups with Zamba2's gated norm by groups, the router to
``DeepseekV3TopkRouter`` and the whole to a loop over tokens.

``N(x; g) = x * rsqrt(mean x^2 + layer_norm_epsilon) * g``; no bias on any
projection, no embedding or residual multiplier. A layer is ONE sub-block:
``h_{l+1} = h_l + Mixer_l(N(h_l; norm_l))``, the mixer by
``hybrid_override_pattern[l]``; logits ``= N(h_L; norm_f) W_head`` (untied).

* ``M``, Mamba-2: ``[z | xBC | dt] = u W_in`` (``d_inner | d_inner + 2 g N |
  heads``, ``d_inner = mamba_num_heads x mamba_head_dim``, NOT ``expand x
  hidden_size``); ``xBC = silu(conv1d(xBC) + b)``, depthwise causal of width
  ``conv_kernel``; ``[x | B | C]`` = ``d_inner | g x N | g x N``; ``dt =
  softplus(dt + dt_bias)``, no clamp; per head ``h`` in group ``h // (heads /
  g)``: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T``, ``A_h =
  -exp(A_log_h)``, ``y_t = S_t C_t + D_h x_t``; ``y = w * GroupRMS_g(y *
  silu(z))``, the norm over EACH group's ``d_inner / g`` channels, gate first;
  ``out = y W_out``.
* ``*``: grouped-query softmax attention, ``head_dim`` as published, scale
  ``head_dim ** -0.5``, causal, NO rotary embedding.
* ``E``: ``s = sigmoid(u W_r)`` in float32 over the router's columns; the top
  ``num_experts_per_tok`` of ``s + e_score_correction_bias``; ``w = s[picked]
  / (sum + 1e-20) x routed_scaling_factor``; ``sum_k w_k relu²(u W_up^k)
  W_down^k + relu²(u W_up^s) W_down^s`` (the shared expert, ungated, always).
* ``-``: ``relu²(u W_up) W_down`` at ``intermediate_size`` (older rows).

A SHARE of an expert-parallel layer: ``n_routed_experts`` is what the weights
hold, ``router_n_routed_experts`` what the router scores (default: the same),
``first_expert`` the first held; a pick of an expert held elsewhere weighs in
the renormalisation and adds nothing here. The shared expert is whole.

Departures from the published module, layout only: per-layer tensors stacked
over the layers that carry them, experts over a second axis
(``harness/weights.py``); every held expert's output is computed and the
unpicked weighted by zero. ``A_log`` and ``dt_bias`` are drawn so that the
step size is log-uniform over ``time_step_min .. time_step_max`` and ``A``
over 1 .. 16 (memories of 0.6 to 1000 tokens: a broken carry shows);
``e_score_correction_bias`` non-zero so that a bias that was dropped shows.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import math

import jax
import jax.numpy as jnp

from harness.reference import linear, rms_norm, rope

L = "backbone.layers.{i}."
MIX = L + "mixer."
EXPERT = MIX + "experts.{e}."
SHARED = MIX + "shared_experts."
TOPK_NORM_EPS = 1e-20

#: faults a comparison against the served path must catch
CONTROLS = (
    "relu_for_relu2",      # relu(u W_up) W_down, experts and shared expert
    "gated_expert",        # relu²(u W_up) * (u W_up): a gate that is not there
    "norm_whole_width",    # the gated norm over d_inner, not by group
    "norm_before_gate",    # w * GroupRMS(y) * silu(z)
    "bc_group0",           # every head reads group 0's B and C
    "bias_dropped",        # the top-k of s alone
    "renorm_dropped",      # w = s[picked], not divided by its sum
    "scaling_dropped",     # no routed_scaling_factor
    "shared_dropped",      # no shared expert
    "softmax_router",      # s = softmax(u W_r)
    "rotary_applied",      # half-rotation rotary on q and k, rope_theta
)

#: queries a block of :func:`_attention` (None: all at once); a caller with
#: long sequences sets it, which changes the order of evaluation only
ATTEND_BLOCK = None


def _layers(cfg, letter):
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern must name every layer")
    return [i for i, c in enumerate(pattern) if c == letter]


def _geometry(cfg):
    nh, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return nh * hd, nh, hd, g, n


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def weight_shapes(cfg):
    hid, vocab = cfg["hidden_size"], cfg["vocab_size"]
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    d_inner, nh, _, g, n = _geometry(cfg)
    conv_dim, k = d_inner + 2 * g * n, cfg["conv_kernel"]
    held = cfg.get("n_routed_experts", 0)
    scored = cfg.get("router_n_routed_experts") or held
    table = {
        "backbone.embeddings.weight": {"shape": (vocab, hid),
                                       "init": "normal"},
        "backbone.norm_f.weight": {"shape": (hid,), "init": "norm"},
        "lm_head.weight": {"shape": (vocab, hid), "init": "normal"},
        L + "norm.weight": {"shape": (cfg["num_hidden_layers"], hid),
                            "init": "norm"},
    }

    def add(layers, names):
        for name, shape, init in names:
            if layers:
                table[name] = {"shape": (len(layers),) + shape, "init": init,
                               "layers": layers}
    # the mixer's own parameters as torch and the published module draw
    # them: the taps as nn.Conv1d does (uniform within 1 / sqrt(K): at N(0,
    # 0.02) x, B and C would shrink fifty-fold and a broken carry could not
    # show), dt log-uniform over time_step_min .. max (softplus(b) = e^b to
    # 5 % at b <= -2.3), A log-uniform over 1 .. 16, D = 1
    bound = float(k) ** -0.5
    lo, hi = (math.log(cfg["time_step_min"]),
              math.log(cfg["time_step_max"]))
    add(_layers(cfg, "M"), [
        (MIX + "in_proj.weight", (2 * d_inner + 2 * g * n + nh, hid),
         "normal"),
        (MIX + "conv1d.weight", (conv_dim, 1, k), ["uniform", -bound, bound]),
        (MIX + "conv1d.bias", (conv_dim,), ["uniform", -bound, bound]),
        (MIX + "dt_bias", (nh,), ["uniform", lo, hi]),
        (MIX + "A_log", (nh,), ["uniform", 0.0, 2.77]),
        (MIX + "D", (nh,), "ones"),
        (MIX + "norm.weight", (d_inner,), "norm"),
        (MIX + "out_proj.weight", (hid, d_inner), "normal")])
    add(_layers(cfg, "*"), [
        (MIX + "q_proj.weight", (nq * d, hid), "normal"),
        (MIX + "k_proj.weight", (nkv * d, hid), "normal"),
        (MIX + "v_proj.weight", (nkv * d, hid), "normal"),
        (MIX + "o_proj.weight", (hid, nq * d), "normal")])
    inter_e = cfg.get("moe_intermediate_size", 0)
    inter_s = cfg.get("n_shared_experts", 1) * cfg.get(
        "moe_shared_expert_intermediate_size", 0)
    add(_layers(cfg, "E"), [
        (MIX + "gate.weight", (scored, hid), "normal"),
        (MIX + "gate.e_score_correction_bias", (scored,),
         ["uniform", -0.2, 0.2]),
        (EXPERT + "up_proj.weight", (held, inter_e, hid), "normal"),
        (EXPERT + "down_proj.weight", (held, hid, inter_e), "normal")]
        + ([(SHARED + "up_proj.weight", (inter_s, hid), "normal"),
            (SHARED + "down_proj.weight", (hid, inter_s), "normal")]
           if inter_s else []))
    add(_layers(cfg, "-"), [
        (MIX + "up_proj.weight", (cfg.get("intermediate_size", 0), hid),
         "normal"),
        (MIX + "down_proj.weight", (hid, cfg.get("intermediate_size", 0)),
         "normal")])
    return table


def _attention(cfg, w, j, h, control):
    b, s, _ = h.shape
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    pos = jnp.arange(s)
    q = linear(h, w[MIX + "q_proj.weight"][j]).reshape(b, s, nq, d)
    k = linear(h, w[MIX + "k_proj.weight"][j]).reshape(b, s, nkv, d)
    v = linear(h, w[MIX + "v_proj.weight"][j]).reshape(b, s, nkv, d)
    if control == "rotary_applied":
        q = rope(q, pos, float(cfg["rope_theta"]))
        k = rope(k, pos, float(cfg["rope_theta"]))
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    block = ATTEND_BLOCK or s
    outs = []
    for lo in range(0, s, block):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:lo + block],
                            k) * d ** -0.5
        seen = pos[lo:lo + block, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, nq * d)
    return linear(out, w[MIX + "o_proj.weight"][j])


def _mamba(cfg, w, j, u, control):
    """``(out (B, S, H), S_last (B, heads, head_dim, N))``."""
    b, s, _ = u.shape
    d_inner, nh, hd, g, n = _geometry(cfg)
    gn, k = g * n, cfg["conv_kernel"]
    f32 = jnp.float32
    zxbcdt = linear(u, w[MIX + "in_proj.weight"][j])
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    taps = w[MIX + "conv1d.weight"][j].astype(f32)[:, 0, :]        # (C, K)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * taps[:, i] for i in range(k))
    xbc = jax.nn.silu(conv + w[MIX + "conv1d.bias"][j].astype(f32))
    x = xbc[..., :d_inner].reshape(b, s, nh, hd)
    bm = xbc[..., d_inner:d_inner + gn].reshape(b, s, g, n)
    cm = xbc[..., d_inner + gn:].reshape(b, s, g, n)
    if control == "bc_group0":
        bm, cm = (jnp.repeat(t[:, :, :1], g, axis=2) for t in (bm, cm))
    # head h reads group h // (heads / groups)
    bm, cm = (jnp.repeat(t, nh // g, axis=2) for t in (bm, cm))
    dt = jax.nn.softplus(dt + w[MIX + "dt_bias"][j].astype(f32))   # (b,s,nh)
    a = -jnp.exp(w[MIX + "A_log"][j].astype(f32))                  # (nh,)
    d_skip = w[MIX + "D"][j].astype(f32)

    def step(state, t):                          # state (b, nh, hd, n)
        x_t, b_t, c_t, dt_t = t
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.einsum("bhdn,bhn->bhd", state, c_t) + d_skip[:, None] * x_t
        return state, y_t

    def time_first(t):
        return jnp.moveaxis(t, 1, 0)
    last, y = jax.lax.scan(step, jnp.zeros((b, nh, hd, n), f32),
                           tuple(map(time_first, (x, bm, cm, dt))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d_inner)
    gain, eps = w[MIX + "norm.weight"][j], cfg["layer_norm_epsilon"]
    gate = jax.nn.silu(z)
    groups = 1 if control == "norm_whole_width" else g

    def group_norm(t):
        t = t.reshape(b, s, groups, d_inner // groups)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)
        return t.reshape(b, s, d_inner) * gain.astype(f32)
    y = (group_norm(y) * gate if control == "norm_before_gate"
         else group_norm(y * gate))
    return linear(y, w[MIX + "out_proj.weight"][j]), last


def route(cfg, logits, bias, control=None):
    """``(weights (N, k), picked (N, k), margin (N,))`` of router logits
    (N, E): the margin is the relative gap between the last picked and the
    first left out of ``s + bias``, the quantity that picks."""
    k = cfg["num_experts_per_tok"]
    s = (jax.nn.softmax(logits, axis=-1) if control == "softmax_router"
         else jax.nn.sigmoid(logits))
    pick_by = s if control == "bias_dropped" else s + bias
    edge, picked = jax.lax.top_k(pick_by, k + 1)
    margin = (edge[:, k - 1] - edge[:, k]) / jnp.maximum(
        jnp.abs(edge[:, k - 1]), 1e-20)
    picked = picked[:, :k]
    top = jnp.take_along_axis(s, picked, axis=-1)
    if cfg.get("norm_topk_prob", True) and control != "renorm_dropped":
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + TOPK_NORM_EPS)
    if control != "scaling_dropped":
        top = top * cfg.get("routed_scaling_factor", 1.0)
    return top, picked, margin


def _act(u, control):
    """``relu²(u)``, or what a control makes of it."""
    if control == "relu_for_relu2":
        return jax.nn.relu(u)
    return _relu2(u) * u if control == "gated_expert" else _relu2(u)


def _plain_mlp(x, up, down, control):
    return linear(_act(linear(x, up), control), down)


def _experts(cfg, w, j, h, control):
    b, s, hid = h.shape
    x = h.reshape(b * s, hid)
    held = cfg["n_routed_experts"]
    scored = cfg.get("router_n_routed_experts") or held
    first = cfg.get("first_expert", 0)
    top, picked, margin = route(
        cfg, linear(x, w[MIX + "gate.weight"][j]),
        w[MIX + "gate.e_score_correction_bias"][j].astype(jnp.float32),
        control)
    # (N, held): a pick's weight on the experts this share holds, 0 if
    # unpicked; a pick of an expert held elsewhere adds nothing here
    weight_of = jnp.sum(jax.nn.one_hot(picked, scored) * top[..., None],
                        axis=1)[:, first:first + held]
    up = w[EXPERT + "up_proj.weight"][j].astype(jnp.float32)     # (E, I, H)
    down = w[EXPERT + "down_proj.weight"][j].astype(jnp.float32)  # (E, H, I)
    act = _act(jnp.einsum("nh,eih->nei", x, up), control)
    out = jnp.einsum("nei,ehi->nh", act * weight_of[..., None], down)
    if (SHARED + "up_proj.weight") in w and control != "shared_dropped":
        out = out + _plain_mlp(x, w[SHARED + "up_proj.weight"][j],
                               w[SHARED + "down_proj.weight"][j], control)
    return out.reshape(b, s, hid), margin.reshape(b, s)


def layer(cfg, w, i, x, control=None):
    """Layer ``i`` over ``x`` (B, S, H): ``(x', margin (B, S), state)``;
    ``margin`` is ``inf`` unless the layer routes, ``state`` the Mamba-2
    state after the last token or None."""
    pattern = cfg["hybrid_override_pattern"]
    kind = pattern[i]
    j = pattern[:i].count(kind)
    h = rms_norm(x, w[L + "norm.weight"][i], cfg["layer_norm_epsilon"])
    margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    state = None
    if kind == "M":
        out, state = _mamba(cfg, w, j, h, control)
    elif kind == "*":
        out = _attention(cfg, w, j, h, control)
    elif kind == "E":
        out, margin = _experts(cfg, w, j, h, control)
    elif kind == "-":
        out = _plain_mlp(h, w[MIX + "up_proj.weight"][j],
                         w[MIX + "down_proj.weight"][j], control)
    else:
        raise ValueError(f"hybrid_override_pattern[{i}] = {kind!r}")
    return x + out, margin, state


def final_hidden(cfg, w, ids, control=None):
    """``(N(h_L; norm_f) (B, S, H), margins (B, S), states)``: what the head
    reads, per position the smallest routing margin over its expert layers,
    and every Mamba-2 layer's state after the last token."""
    x = w["backbone.embeddings.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    states = []
    for i in range(cfg["num_hidden_layers"]):
        x, margin, state = layer(cfg, w, i, x, control)
        margins = jnp.minimum(margins, margin)
        if state is not None:
            states.append(state)
    return (rms_norm(x, w["backbone.norm_f.weight"],
                     cfg["layer_norm_epsilon"]), margins, states)


def forward(cfg, w, ids, with_margins=False, control=None):
    """Float32 logits ``(B, S, vocab)``; with ``with_margins`` also ``(B, S)``
    float32, the smallest routing margin over a position's expert layers."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("the reference has the untied head only")
    x, margins, _ = final_hidden(cfg, w, ids, control)
    logits = linear(x, w["lm_head.weight"])
    return (logits, margins) if with_margins else logits


def final_states(cfg, w, ids):
    """Every Mamba-2 layer's state after the last token of ``ids``, ``(M
    layers, B, heads, head_dim, state)`` float32: what a served sequence's
    state slot is held to."""
    return jnp.stack(final_hidden(cfg, w, ids)[2])
