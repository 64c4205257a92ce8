"""The plain reference of ``model_type`` ``olmo_hybrid`` (Olmo-Hybrid-7B):
float32, the whole sequence through every layer, the linear layers ONE TOKEN
AFTER ANOTHER through the state recurrence (no chunked form, no cache).
Written from the equations of ISSUE 34, which are the gated delta rule as
published (Yang, Kautz, Hatamizadeh: "Gated Delta Networks", 2024) under the
config's ``linear_*`` keys and the Olmo 2 / Olmo 3 block, and from nothing of
this repository's ``modules/ssm.py``. This machine's ``transformers`` has no
``olmo_hybrid``; what was taken by convention is listed under ``assumed`` in
``configs/olmo-hybrid-7b.json``.

Every layer, with ``N`` an RMSNorm of its own weight and NO input norm:
``h = h + N_post_attn(T(h))``, then ``h = h + N_post_ff(W_down(silu(W_gate h)
* W_up h))``; logits ``= W_head N(h_L)`` (untied). ``T`` is, by
``layer_types``:

* ``full_attention``: ``q = N_q(W_q h)``, ``k = N_k(W_k h)`` over the whole
  projected width before the split into heads, causal softmax attention at
  ``head_dim ** -0.5``, no positional embedding, ``W_o``;
* ``linear_attention``: ``[q; k; v] = silu(conv(W_{q,k,v} h))`` with a
  depthwise causal convolution of width ``linear_conv_kernel_dim`` and no
  bias; per head ``q <- q / |q| * d_k ** -0.5``, ``k <- k / |k|`` (eps 1e-6
  inside the root); ``beta = sigmoid(W_b h)``, doubled where
  ``linear_allow_neg_eigval``; ``alpha = exp(-exp(A_log) * softplus(W_a h +
  dt_bias))``; per head ``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t
  S_{t-1})^T k_t)^T`` and ``o_t = S_t^T q_t`` with ``S`` of ``(d_k, d_v)``;
  ``y_t = N_o(o_t) * silu(W_g h_t)`` (the norm over ``d_v`` with one weight
  shared by the heads, THEN the gate) and ``W_o y``.
"""

import jax
import jax.numpy as jnp

from harness.reference import L, linear, rms_norm

ATTN = L + "self_attn."
LIN = L + "linear_attn."
MLP = L + "mlp."
L2_EPS = 1e-6


def _layers(cfg, kind):
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    if set(types) - {"linear_attention", "full_attention"}:
        raise ValueError(f"unknown layer type in {sorted(set(types))}")
    return [i for i, t in enumerate(types) if t == kind]


def _geometry(cfg):
    """``(heads, d_k, d_v)`` of the linear layers."""
    heads = cfg["linear_num_value_heads"]
    if cfg["linear_num_key_heads"] != heads:
        raise ValueError("the reference covers linear_num_key_heads == "
                         "linear_num_value_heads only")
    return heads, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // nq
    inter = cfg["intermediate_size"]
    heads, dk, dv = _geometry(cfg)
    k = cfg["linear_conv_kernel_dim"]
    full = _layers(cfg, "full_attention")
    lin = _layers(cfg, "linear_attention")
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        "lm_head.weight": {"shape": (vocab, hid), "init": "normal"},
        L + "post_attention_layernorm.weight": {"shape": (n_l, hid),
                                                "init": "norm"},
        L + "post_feedforward_layernorm.weight": {"shape": (n_l, hid),
                                                  "init": "norm"},
        MLP + "gate_proj.weight": {"shape": (n_l, inter, hid),
                                   "init": "normal"},
        MLP + "up_proj.weight": {"shape": (n_l, inter, hid),
                                 "init": "normal"},
        MLP + "down_proj.weight": {"shape": (n_l, hid, inter),
                                   "init": "normal"},
    }
    for name, shape, init in (
            ("q_proj.weight", (nq * d, hid), "normal"),
            ("k_proj.weight", (nkv * d, hid), "normal"),
            ("v_proj.weight", (nkv * d, hid), "normal"),
            ("o_proj.weight", (hid, nq * d), "normal"),
            ("q_norm.weight", (nq * d,), "norm"),
            ("k_norm.weight", (nkv * d,), "norm")):
        table[ATTN + name] = {"shape": (len(full),) + shape, "init": init,
                              "layers": full}
    # the mixer's own parameters: the depthwise convolutions as nn.Conv1d
    # draws them (uniform within 1/sqrt(width)); the decay alpha = exp(-A *
    # softplus(a + dt_bias)) with A = 1..16 and softplus(dt_bias) = 1e-3..1e-1
    # (HF's Mamba-2 / Gated DeltaNet ranges), so a head forgets in a few
    # tokens or holds a thousand: drawn as normals every state would die in
    # two tokens and no comparison could see a broken carry. a_proj is drawn
    # narrow (|w| <= 0.01: W_a h is ~0.4 x the rms of h) so that the token's
    # own term moves the rate without drowning dt_bias; b_proj as any linear
    # (W_b h ~ N(0, 1.2 rms): beta spreads over all of (0, 2)).
    bound = float(k) ** -0.5
    for name, shape, init in (
            ("q_proj.weight", (heads * dk, hid), "normal"),
            ("k_proj.weight", (heads * dk, hid), "normal"),
            ("v_proj.weight", (heads * dv, hid), "normal"),
            ("a_proj.weight", (heads, hid), ["uniform", -0.01, 0.01]),
            ("b_proj.weight", (heads, hid), "normal"),
            ("g_proj.weight", (heads * dv, hid), "normal"),
            ("o_proj.weight", (hid, heads * dv), "normal"),
            ("q_conv1d.weight", (heads * dk, 1, k),
             ["uniform", -bound, bound]),
            ("k_conv1d.weight", (heads * dk, 1, k),
             ["uniform", -bound, bound]),
            ("v_conv1d.weight", (heads * dv, 1, k),
             ["uniform", -bound, bound]),
            ("A_log", (heads,), ["uniform", 0.0, 2.77]),
            ("dt_bias", (heads,), ["uniform", -6.9, -2.25]),
            ("o_norm.weight", (dv,), "norm")):
        table[LIN + name] = {"shape": (len(lin),) + shape, "init": init,
                             "layers": lin}
    return table


def _attention(cfg, w, j, h):
    b, s, hid = h.shape
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // nq
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    q = rms_norm(linear(h, w[ATTN + "q_proj.weight"][j]),
                 w[ATTN + "q_norm.weight"][j], eps).reshape(b, s, nq, d)
    k = rms_norm(linear(h, w[ATTN + "k_proj.weight"][j]),
                 w[ATTN + "k_norm.weight"][j], eps).reshape(b, s, nkv, d)
    v = linear(h, w[ATTN + "v_proj.weight"][j]).reshape(b, s, nkv, d)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return linear(out.reshape(b, s, nq * d), w[ATTN + "o_proj.weight"][j])


def _causal_conv(x, weight):
    """Depthwise causal convolution of ``x`` (B, S, C) with the published
    ``Conv1d.weight`` (C, 1, K): ``out_t = sum_i w[:, i] x_{t - (K-1) + i}``,
    zeros before the sequence."""
    k, s = weight.shape[-1], x.shape[1]
    taps = weight.astype(jnp.float32)[:, 0, :]                    # (C, K)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * taps[:, i] for i in range(k))


def _l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule_inputs(cfg, w, j, h):
    """``(q, k, v, alpha, beta, gate)`` of linear layer ``j`` (its index
    among the linear layers) over ``h`` (B, S, hidden): q, k ``(B, S, heads,
    d_k)`` normalised (q scaled), v ``(B, S, heads, d_v)``, alpha and beta
    ``(B, S, heads)``, gate ``(B, S, heads, d_v)`` before its silu."""
    b, s, _ = h.shape
    heads, dk, dv = _geometry(cfg)
    f32 = jnp.float32

    def conv_branch(name, width):
        x = jax.nn.silu(_causal_conv(
            linear(h, w[LIN + name + "_proj.weight"][j]),
            w[LIN + name + "_conv1d.weight"][j]))
        return x.reshape(b, s, heads, width)
    q = _l2_normalise(conv_branch("q", dk)) * dk ** -0.5
    k = _l2_normalise(conv_branch("k", dk))
    v = conv_branch("v", dv)
    beta = jax.nn.sigmoid(linear(h, w[LIN + "b_proj.weight"][j]))
    if cfg.get("linear_allow_neg_eigval"):
        beta = 2.0 * beta
    rate = jnp.exp(w[LIN + "A_log"][j].astype(f32))
    alpha = jnp.exp(-rate * jax.nn.softplus(
        linear(h, w[LIN + "a_proj.weight"][j])
        + w[LIN + "dt_bias"][j].astype(f32)))
    gate = linear(h, w[LIN + "g_proj.weight"][j]).reshape(b, s, heads, dv)
    return q, k, v, alpha, beta, gate


def delta_rule(q, k, v, alpha, beta, state=None):
    """The gated delta rule, token by token: ``(o (B, S, heads, d_v), S_last
    (B, heads, d_k, d_v))`` from ``state`` (zeros where None)."""
    b, _, heads, dk = k.shape
    dv = v.shape[-1]

    def step(st, t):
        q_t, k_t, v_t, a_t, b_t = t
        st = st * a_t[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", st, k_t)
        st = st + (k_t[..., :, None]
                   * (b_t[..., None] * (v_t - read))[..., None, :])
        return st, jnp.einsum("bhkv,bhk->bhv", st, q_t)

    def time_first(t):
        return jnp.moveaxis(t, 1, 0)
    if state is None:
        state = jnp.zeros((b, heads, dk, dv), jnp.float32)
    last, o = jax.lax.scan(step, state, tuple(
        time_first(t) for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1), last


def _linear_attention(cfg, w, j, h):
    b, s, _ = h.shape
    heads, _, dv = _geometry(cfg)
    q, k, v, alpha, beta, gate = delta_rule_inputs(cfg, w, j, h)
    o, last = delta_rule(q, k, v, alpha, beta)
    y = rms_norm(o, w[LIN + "o_norm.weight"][j], cfg["rms_norm_eps"]) \
        * jax.nn.silu(gate)
    return linear(y.reshape(b, s, heads * dv),
                  w[LIN + "o_proj.weight"][j]), last


def _walk(cfg, w, ids):
    """``(logits (B, S, vocab), states)``: ``states`` is each linear layer's
    ``S`` after the last token, ``(B, heads, d_k, d_v)``."""
    eps = cfg["rms_norm_eps"]
    full = _layers(cfg, "full_attention")
    lin = _layers(cfg, "linear_attention")
    h = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    states = []
    for i in range(cfg["num_hidden_layers"]):
        if i in full:
            mixed = _attention(cfg, w, full.index(i), h)
        else:
            mixed, last = _linear_attention(cfg, w, lin.index(i), h)
            states.append(last)
        h = h + rms_norm(mixed, w[L + "post_attention_layernorm.weight"][i],
                         eps)
        mlp = linear(jax.nn.silu(linear(h, w[MLP + "gate_proj.weight"][i]))
                     * linear(h, w[MLP + "up_proj.weight"][i]),
                     w[MLP + "down_proj.weight"][i])
        h = h + rms_norm(mlp, w[L + "post_feedforward_layernorm.weight"][i],
                         eps)
    h = rms_norm(h, w["model.norm.weight"], eps)
    return linear(h, w["lm_head.weight"]), states


def forward(cfg, w, ids, with_margins=False):
    """Float32 logits ``(B, S, vocab)``; nothing is routed, so the margins
    are ``inf`` everywhere."""
    logits, _ = _walk(cfg, w, ids)
    if with_margins:
        return logits, jnp.full(ids.shape, jnp.inf, jnp.float32)
    return logits


def final_states(cfg, w, ids):
    """The state every linear layer holds after the last token of ``ids``,
    ``(linear layers, B, heads, d_k, d_v)`` in float32: what a served
    sequence's state slot is held to (the logits of a short run cannot tell
    the precision the state is carried in; the state can)."""
    return jnp.stack(_walk(cfg, w, ids)[1])
