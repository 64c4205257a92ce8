"""The plain reference of ``model_type`` ``qwen3_next``
(Qwen3-Next-80B-A3B): float32, the whole sequence through every layer, the
linear layers ONE TOKEN AFTER ANOTHER through the state recurrence (no
chunked form, no cache). Written from the equations of ISSUE 36, which are
``transformers``' ``modeling_qwen3_next.py`` (4.57.6) read as mathematics,
and from nothing of this repository's ``modules/``;
``tests/reference_cases/qwen3_next.json`` holds it to
``Qwen3NextForCausalLM`` at a toy size.

Pre-norm blocks; every norm ``N(x) = x * rsqrt(mean x^2 + eps) * (1 + w)``:
``h = h + T(N_in h)``, then ``h = h + M(N_post h)``; logits ``= W_head
N(h_L)`` (untied). Layer ``i`` is ``layer_types[i]`` where the config spells
the types out, else full attention where ``(i + 1) %
full_attention_interval == 0`` and linear attention elsewhere.

* ``full_attention``: ``q_proj`` yields per head ``[query | gate]``; per-head
  ``N`` over ``head_dim`` on q and k; rotary (half-rotation form) on the first
  ``partial_rotary_factor * head_dim`` lanes; causal softmax at ``head_dim **
  -0.5``, grouped queries; ``W_o (attn * sigmoid(gate))``.
* ``linear_attention`` (Gated DeltaNet): ``in_proj_qkvz`` and ``in_proj_ba``
  are interleaved per KEY head: group ``[q d_k | k d_k | v r d_v | z r d_v]``
  and ``[b r | a r]`` with ``r`` value heads a key head. One depthwise causal
  convolution (width ``linear_conv_kernel_dim``, no bias) and silu over ``[q |
  k | v]``; q, k l2-normalised (eps 1e-6 inside the root), q scaled by ``d_k
  ** -0.5``, both repeated over the ``r`` value heads of their key head
  (``repeat_interleave``: value head ``j`` reads key head ``j // r``); ``beta
  = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; per value head ``S
  <- exp(g) S; S <- S + beta k (v - S^T k)^T; o = S^T q``; then ``w *
  rmsnorm(o) * silu(z)`` over ``d_v`` (plain ``w``) and ``out_proj``.
* the sparse block ``M``: router softmax over ALL ``router_num_experts``
  experts, top ``num_experts_per_tok``, renormalised over those (``norm_topk_prob``);
  ``sum_e p_e down_e(silu(gate_e x) * up_e x)``; plus ``sigmoid(w_sg . x) *
  shared_expert(x)``.

ONE CHIP'S SHARE. ``num_experts`` is the number of experts the weights HOLD.
Where the config also gives ``router_num_experts`` (the published count) and
``first_expert``, the router scores all ``router_num_experts`` and the sum runs
over the held experts ``first_expert .. first_expert + num_experts - 1`` only:
the other chips' part is left out (it is nobody's here), the shared expert
and its gate are whole. Without the key every expert is held.

Left out: the multi-token-prediction head (``Qwen3NextForCausalLM`` loads
none).
"""

import jax
import jax.numpy as jnp

from harness.reference import EXPERT, L, linear, rope, swiglu

ATTN = L + "self_attn."
LIN = L + "linear_attn."
MLP = L + "mlp."
L2_EPS = 1e-6
FULL, LINEAR = "full_attention", "linear_attention"


def layer_types(cfg):
    types = cfg.get("layer_types")
    if not types:
        every = cfg.get("full_attention_interval", 4)
        types = [FULL if (i + 1) % every == 0 else LINEAR
                 for i in range(cfg["num_hidden_layers"])]
    if len(types) != cfg["num_hidden_layers"] or set(types) - {FULL, LINEAR}:
        raise ValueError(f"layer_types must name every layer: {types}")
    return list(types)


def _layers(cfg, kind):
    return [i for i, t in enumerate(layer_types(cfg)) if t == kind]


def _geometry(cfg):
    """``(key heads, value heads, d_k, d_v)`` of the linear layers."""
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    if nv % nk:
        raise ValueError("value heads must be a multiple of key heads")
    return nk, nv, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]


def share(cfg):
    """``(experts the router scores, experts held, the first held)``."""
    held = cfg["num_experts"]
    routed = cfg.get("router_num_experts") or held
    first = cfg.get("first_expert") or 0
    if not 0 <= first <= routed - held:
        raise ValueError(f"experts {first}.. of {held} held, {routed} routed")
    return routed, held, first


def norm1p(x, weight, eps):
    """``x * rsqrt(mean x^2 + eps) * (1 + w)`` in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + weight.astype(jnp.float32))


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    nk, nv, dk, dv = _geometry(cfg)
    k = cfg["linear_conv_kernel_dim"]
    routed, held, _ = share(cfg)
    inter, shared = (cfg["moe_intermediate_size"],
                     cfg["shared_expert_intermediate_size"])
    full, lin = _layers(cfg, FULL), _layers(cfg, LINEAR)
    # a (1 + w) norm is drawn like every other norm of the harness, w = 1 +
    # 0.1 N(0, 1) (the checkpoint's sit near 0): its scale is then 2 +- 0.1,
    # and read as plain w it halves, which no comparison can miss
    one_plus = "norm"
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": one_plus},
        "lm_head.weight": {"shape": (vocab, hid), "init": "normal"},
        L + "input_layernorm.weight": {"shape": (n_l, hid),
                                       "init": one_plus},
        L + "post_attention_layernorm.weight": {"shape": (n_l, hid),
                                                "init": one_plus},
        MLP + "gate.weight": {"shape": (n_l, routed, hid), "init": "normal"},
        EXPERT + "gate_proj.weight": {"shape": (n_l, held, inter, hid),
                                      "init": "normal"},
        EXPERT + "up_proj.weight": {"shape": (n_l, held, inter, hid),
                                    "init": "normal"},
        EXPERT + "down_proj.weight": {"shape": (n_l, held, hid, inter),
                                      "init": "normal"},
        MLP + "shared_expert.gate_proj.weight": {"shape": (n_l, shared, hid),
                                                 "init": "normal"},
        MLP + "shared_expert.up_proj.weight": {"shape": (n_l, shared, hid),
                                               "init": "normal"},
        MLP + "shared_expert.down_proj.weight": {"shape": (n_l, hid, shared),
                                                 "init": "normal"},
        MLP + "shared_expert_gate.weight": {"shape": (n_l, 1, hid),
                                            "init": "normal"},
    }
    for name, shape, init in (
            ("q_proj.weight", (nq * d * 2, hid), "normal"),
            ("k_proj.weight", (nkv * d, hid), "normal"),
            ("v_proj.weight", (nkv * d, hid), "normal"),
            ("o_proj.weight", (hid, nq * d), "normal"),
            ("q_norm.weight", (d,), one_plus),
            ("k_norm.weight", (d,), one_plus)):
        table[ATTN + name] = {"shape": (len(full),) + shape, "init": init,
                              "layers": full}
    # the mixer's own parameters as olmo_hybrid's reference draws them (the
    # same mixer): the convolution as nn.Conv1d does, A = 1..16, softplus(
    # dt_bias) = 1e-3..1e-1, so a head forgets in a few tokens or holds a
    # thousand. in_proj_ba feeds both a (added to dt_bias) and b (sigmoid ->
    # beta): one tensor, one initialiser; N(0, 0.02) over the hidden width
    # gives b ~ N(0, 1) (beta over all of (0, 1)) and moves the rate by a
    # factor e^-1..e^1 around what dt_bias sets
    bound = float(k) ** -0.5
    conv_dim = 2 * nk * dk + nv * dv
    for name, shape, init in (
            ("in_proj_qkvz.weight", (2 * nk * dk + 2 * nv * dv, hid),
             "normal"),
            ("in_proj_ba.weight", (2 * nv, hid), "normal"),
            ("conv1d.weight", (conv_dim, 1, k), ["uniform", -bound, bound]),
            ("A_log", (nv,), ["uniform", 0.0, 2.77]),
            ("dt_bias", (nv,), ["uniform", -6.9, -2.25]),
            ("norm.weight", (dv,), "norm"),
            ("out_proj.weight", (hid, nv * dv), "normal")):
        table[LIN + name] = {"shape": (len(lin),) + shape, "init": init,
                             "layers": lin}
    return table


def _attention(cfg, w, j, h):
    b, s, _ = h.shape
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    rot = int(d * cfg.get("partial_rotary_factor", 1.0))
    pos = jnp.arange(s)
    qg = linear(h, w[ATTN + "q_proj.weight"][j]).reshape(b, s, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, s, nq * d)
    q = norm1p(q, w[ATTN + "q_norm.weight"][j], eps)
    k = norm1p(linear(h, w[ATTN + "k_proj.weight"][j]).reshape(b, s, nkv, d),
               w[ATTN + "k_norm.weight"][j], eps)
    v = linear(h, w[ATTN + "v_proj.weight"][j]).reshape(b, s, nkv, d)

    def rotary(x):
        # the first `rot` lanes rotate (theta over rot, not over head_dim)
        return jnp.concatenate(
            [rope(x[..., :rot], pos, cfg["rope_theta"]), x[..., rot:]],
            axis=-1)
    q, k = rotary(q), rotary(k)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return linear(out.reshape(b, s, nq * d) * jax.nn.sigmoid(gate),
                  w[ATTN + "o_proj.weight"][j])


def _causal_conv(x, weight):
    """Depthwise causal convolution of ``x`` (B, S, C) with the published
    ``Conv1d.weight`` (C, 1, K), zeros before the sequence."""
    k, s = weight.shape[-1], x.shape[1]
    taps = weight.astype(jnp.float32)[:, 0, :]                    # (C, K)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * taps[:, i] for i in range(k))


def _l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule_inputs(cfg, w, j, h):
    """``(q, k, v, g, beta, z)`` of linear layer ``j`` (its index among the
    linear layers) over ``h`` (B, S, hidden): q, k ``(B, S, value heads,
    d_k)`` normalised (q scaled) and already repeated over the value heads,
    v and z ``(B, S, value heads, d_v)``, g (log decay) and beta ``(B, S,
    value heads)``."""
    b, s, _ = h.shape
    nk, nv, dk, dv = _geometry(cfg)
    r = nv // nk
    f32 = jnp.float32
    qkvz = linear(h, w[LIN + "in_proj_qkvz.weight"][j]).reshape(
        b, s, nk, 2 * dk + 2 * r * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = linear(h, w[LIN + "in_proj_ba.weight"][j]).reshape(b, s, nk, 2 * r)
    beta_in, a = ba[..., :r].reshape(b, s, nv), ba[..., r:].reshape(b, s, nv)
    mixed = jnp.concatenate([q.reshape(b, s, nk * dk),
                             k.reshape(b, s, nk * dk),
                             v.reshape(b, s, nv * dv)], axis=-1)
    mixed = jax.nn.silu(_causal_conv(mixed, w[LIN + "conv1d.weight"][j]))
    q = mixed[..., :nk * dk].reshape(b, s, nk, dk)
    k = mixed[..., nk * dk:2 * nk * dk].reshape(b, s, nk, dk)
    v = mixed[..., 2 * nk * dk:].reshape(b, s, nv, dv)
    q = jnp.repeat(_l2_normalise(q) * dk ** -0.5, r, axis=2)
    k = jnp.repeat(_l2_normalise(k), r, axis=2)
    g = -jnp.exp(w[LIN + "A_log"][j].astype(f32)) * jax.nn.softplus(
        a + w[LIN + "dt_bias"][j].astype(f32))
    return q, k, v, g, jax.nn.sigmoid(beta_in), z.reshape(b, s, nv, dv)


def delta_rule(q, k, v, g, beta, state=None):
    """The gated delta rule, token by token: ``(o (B, S, heads, d_v), S_last
    (B, heads, d_k, d_v))`` from ``state`` (zeros where None)."""
    b, _, heads, dk = k.shape
    dv = v.shape[-1]

    def step(st, t):
        q_t, k_t, v_t, g_t, b_t = t
        st = st * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", st, k_t)
        st = st + (k_t[..., :, None]
                   * (b_t[..., None] * (v_t - read))[..., None, :])
        return st, jnp.einsum("bhkv,bhk->bhv", st, q_t)

    if state is None:
        state = jnp.zeros((b, heads, dk, dv), jnp.float32)
    last, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def _linear_attention(cfg, w, j, h):
    b, s, _ = h.shape
    _, nv, _, dv = _geometry(cfg)
    q, k, v, g, beta, z = delta_rule_inputs(cfg, w, j, h)
    o, last = delta_rule(q, k, v, g, beta)
    y = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                           + cfg["rms_norm_eps"])
         * w[LIN + "norm.weight"][j].astype(jnp.float32)) * jax.nn.silu(z)
    return linear(y.reshape(b, s, nv * dv),
                  w[LIN + "out_proj.weight"][j]), last


def routing(cfg, w, i, x):
    """``(weights (B, S, k), experts (B, S, k), margin (B, S))`` of layer
    ``i``: the router over ALL the experts it scores; the margin is the gap
    between the last probability kept and the first one dropped, as a share
    of the last kept (before renormalising: ``harness/reference.py``'s
    measure of how clearly a routing is decided)."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(linear(x, w[MLP + "gate.weight"][i]), axis=-1)
    top, idx = jax.lax.top_k(probs, k + 1)
    margin = (top[..., k - 1] - top[..., k]) / top[..., k - 1]
    top, idx = top[..., :k], idx[..., :k]
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top, idx, margin


def sparse_block(cfg, w, i, x):
    """``(y, margin)``: the held experts' part of the routed sum, plus the
    gated shared expert."""
    routed, held, first = share(cfg)
    top, idx, margin = routing(cfg, w, i, x)
    b, s, _ = x.shape
    combine = jnp.zeros((b, s, routed), jnp.float32).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        idx].add(top)[..., first:first + held]
    f32 = jnp.float32
    hidden = jax.nn.silu(jnp.einsum(
        "bsh,eih->bsei", x, w[EXPERT + "gate_proj.weight"][i].astype(f32))) \
        * jnp.einsum("bsh,eih->bsei", x,
                     w[EXPERT + "up_proj.weight"][i].astype(f32))
    y = jnp.einsum("bsei,ehi,bse->bsh", hidden,
                   w[EXPERT + "down_proj.weight"][i].astype(f32), combine)
    s_out = swiglu(x, w[MLP + "shared_expert.gate_proj.weight"][i],
                    w[MLP + "shared_expert.up_proj.weight"][i],
                    w[MLP + "shared_expert.down_proj.weight"][i])
    s_gate = jax.nn.sigmoid(linear(x, w[MLP + "shared_expert_gate.weight"][i]))
    return y + s_gate * s_out, margin


def _walk(cfg, w, ids):
    """``(logits, margins (B, S), states)``: ``states`` is each linear
    layer's ``S`` after the last token, ``(B, value heads, d_k, d_v)``."""
    eps = cfg["rms_norm_eps"]
    full, lin = _layers(cfg, FULL), _layers(cfg, LINEAR)
    h = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    states = []
    for i in range(cfg["num_hidden_layers"]):
        x = norm1p(h, w[L + "input_layernorm.weight"][i], eps)
        if i in full:
            mixed = _attention(cfg, w, full.index(i), x)
        else:
            mixed, last = _linear_attention(cfg, w, lin.index(i), x)
            states.append(last)
        h = h + mixed
        y, margin = sparse_block(
            cfg, w, i, norm1p(h, w[L + "post_attention_layernorm.weight"][i],
                              eps))
        h = h + y
        margins = jnp.minimum(margins, margin)
    h = norm1p(h, w["model.norm.weight"], eps)
    return linear(h, w["lm_head.weight"]), margins, states


def forward(cfg, w, ids, with_margins=False):
    """Float32 logits ``(B, S, vocab)``; with ``with_margins`` also, per
    position, the least relative gap over its layers between the last
    router probability kept and the first dropped, over ALL the experts
    scored."""
    logits, margins, _ = _walk(cfg, w, ids)
    return (logits, margins) if with_margins else logits


def final_states(cfg, w, ids):
    """The state every linear layer holds after the last token of ``ids``,
    ``(linear layers, B, value heads, d_k, d_v)`` in float32: what a served
    sequence's state slot is held to."""
    return jnp.stack(_walk(cfg, w, ids)[2])
