"""The plain reference of ``model_type`` ``lfm2_moe`` (LiquidAI/LFM2-8B-A1B):
float32, the whole sequence through every layer, no cache, no kernels, the
short convolution one token after another over a window of its last inputs.
Written from the equations of ISSUE 61 (the catalog row's config, its
``described_as``, transformers' ``modeling_lfm2.py`` for everything the dense
sibling shares and the published ``Lfm2MoeSparseMoeBlock`` as recalled for the
routing), and from nothing of this repository's ``modules/``;
``tests/test_reference_lfm2_moe.py`` holds it to ``Lfm2ForCausalLM`` with
every layer dense, and its routing to a literal transcription.

``N(x; g) = x * rsqrt(mean x^2 + norm_eps) * g``; no bias anywhere
(``conv_bias`` false); pre-norm residual blocks ``x += op(N(x;
operator_norm)); x += ffn(N(x; ffn_norm))``; logits ``= N(x_L;
embedding_norm) Emb^T`` (tied). By ``layer_types``, ``op`` is

* ``conv``: ``[B | C | X] = h W_in`` (that chunk order); ``u_t = B_t * X_t``;
  ``v_t = sum_j w[:, j] u_{t-(K-1)+j}``, a depthwise causal convolution of
  width ``K = conv_L_cache`` with NO activation, zeros before the sequence;
  ``(C_t * v_t) W_out``. What a layer carries from token to token is ``u_{t-1}
  .. u_{t-K+1}`` and nothing else (:func:`final_tails`).
* ``full_attention``: per head ``q = N_d(h W_q; q_layernorm)``, ``k = N_d(h
  W_k; k_layernorm)`` over the head's ``d`` lanes BEFORE the rotary embedding
  (half-rotation form, the whole head, ``rope_theta``); causal ``softmax(q .
  k d^-0.5) v`` over grouped heads; ``out_proj``.

``ffn`` of layers ``0 .. num_dense_layers - 1`` is ``W2 (silu(W1 g) * W3 g)``
at ``intermediate_size``. Of every later layer: ``s = sigmoid(g W_r)``,
float32; ``I`` = the top ``num_experts_per_tok`` of ``s + expert_bias`` (the
bias picks, it does not weigh); ``w = s[I] / (sum s[I] + 1e-6)``
(``norm_topk_prob``) times ``routed_scaling_factor``; ``sum_{e in I} w_e W2_e
(silu(W1_e g) * W3_e g)`` at ``moe_intermediate_size``. No shared expert.

Departures from the published module, layout only: per-layer tensors stacked
over the layers that carry them, experts over a second axis
(``harness/weights.py``); every expert's output is computed and the unpicked
weighted by zero. Assumed, not in the catalog row: ``tie_word_embeddings``
true; the tensor names; the ``1e-6`` (:data:`TOPK_NORM_EPS`); the router and
``expert_bias`` in float32. ``expert_bias`` is drawn non-zero so that a bias
that weighed, or one that was dropped, shows.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import jax
import jax.numpy as jnp

from harness.reference import L, linear, rms_norm, rope

ATTN = L + "self_attn."
CONV = L + "conv."
FFN = L + "feed_forward."
EXPERT = FFN + "experts.{e}."
TOPK_NORM_EPS = 1e-6

#: faults a comparison against the served path must catch
CONTROLS = (
    "bias_weighs",         # w = (s + expert_bias)[I]: the bias weighs too
    "bias_dropped",        # I = top-k of s alone
    "renorm_dropped",      # w = s[I], not divided by its sum
    "b_c_exchanged",       # u = C * X, the output gated by B
    "qk_norm_after_rope",  # rotary first, then the per-head norms
    "dense_as_expert",     # the LAST leading dense layer runs the routed
                           # block (on the first expert layer's weights)
)

#: queries a block of :func:`_attention` (None: all at once); a caller with
#: long sequences sets it, which changes the order of evaluation only
ATTEND_BLOCK = None


def _layers(cfg, kind):
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    return [i for i, t in enumerate(types) if t == kind]


def _dense(cfg):
    return min(cfg["num_dense_layers"], cfg["num_hidden_layers"])


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, k = hid // nq, cfg["conv_L_cache"]
    n_e, inter, inter_e = (cfg["num_experts"], cfg["intermediate_size"],
                           cfg["moe_intermediate_size"])
    attn, conv = _layers(cfg, "full_attention"), _layers(cfg, "conv")
    dense = list(range(_dense(cfg)))
    routed = list(range(_dense(cfg), n_l))
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.embedding_norm.weight": {"shape": (hid,), "init": "norm"},
        L + "operator_norm.weight": {"shape": (n_l, hid), "init": "norm"},
        L + "ffn_norm.weight": {"shape": (n_l, hid), "init": "norm"},
    }

    def add(layers, names):
        for name, shape, init in names:
            if layers:
                table[name] = {"shape": (len(layers),) + shape, "init": init,
                               "layers": layers}
    add(attn, [(ATTN + "q_proj.weight", (nq * d, hid), "normal"),
               (ATTN + "k_proj.weight", (nkv * d, hid), "normal"),
               (ATTN + "v_proj.weight", (nkv * d, hid), "normal"),
               (ATTN + "out_proj.weight", (hid, nq * d), "normal"),
               # spread wide: a rotation keeps a head's norm, so the ORDER
               # of norm and rotary shows only through the weight's spread
               # over the lanes (at 1 +- 0.1 it moved no logit by the bf16
               # gate's tolerance)
               (ATTN + "q_layernorm.weight", (d,), ["uniform", 0.5, 1.5]),
               (ATTN + "k_layernorm.weight", (d,), ["uniform", 0.5, 1.5])])
    # the taps as nn.Conv1d draws them (uniform within 1 / sqrt(K)): drawn
    # N(0, 0.02) the operator would be a fiftieth of the attention's and a
    # broken carry of its tail could not show in the logits
    bound = float(k) ** -0.5
    add(conv, [(CONV + "in_proj.weight", (3 * hid, hid), "normal"),
               (CONV + "conv.weight", (hid, 1, k),
                ["uniform", -bound, bound]),
               (CONV + "out_proj.weight", (hid, hid), "normal")])
    add(dense, [(FFN + "w1.weight", (inter, hid), "normal"),
                (FFN + "w3.weight", (inter, hid), "normal"),
                (FFN + "w2.weight", (hid, inter), "normal")])
    add(routed, [(FFN + "gate.weight", (n_e, hid), "normal"),
                 (FFN + "expert_bias", (n_e,), ["uniform", -0.2, 0.2]),
                 (EXPERT + "w1.weight", (n_e, inter_e, hid), "normal"),
                 (EXPERT + "w3.weight", (n_e, inter_e, hid), "normal"),
                 (EXPERT + "w2.weight", (n_e, hid, inter_e), "normal")])
    return table


def _attention(cfg, w, j, h, control):
    b, s, hid = h.shape
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps, theta = hid // nq, cfg["norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(s)
    q = linear(h, w[ATTN + "q_proj.weight"][j]).reshape(b, s, nq, d)
    k = linear(h, w[ATTN + "k_proj.weight"][j]).reshape(b, s, nkv, d)
    v = linear(h, w[ATTN + "v_proj.weight"][j]).reshape(b, s, nkv, d)
    qn, kn = (w[ATTN + "q_layernorm.weight"][j],
              w[ATTN + "k_layernorm.weight"][j])
    if control == "qk_norm_after_rope":
        q = rms_norm(rope(q, pos, theta), qn, eps)
        k = rms_norm(rope(k, pos, theta), kn, eps)
    else:
        q = rope(rms_norm(q, qn, eps), pos, theta)
        k = rope(rms_norm(k, kn, eps), pos, theta)
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
    return linear(out, w[ATTN + "out_proj.weight"][j])


def _short_conv(cfg, w, j, h, control):
    """``(op (B, S, H), tail (B, K-1, H))``: the operator's output and the
    products ``u`` of the last ``K - 1`` tokens, oldest first."""
    hid, k = cfg["hidden_size"], cfg["conv_L_cache"]
    bcx = linear(h, w[CONV + "in_proj.weight"][j])
    bg, cg, xg = bcx[..., :hid], bcx[..., hid:2 * hid], bcx[..., 2 * hid:]
    if control == "b_c_exchanged":
        bg, cg = cg, bg
    u = bg * xg
    taps = w[CONV + "conv.weight"][j].astype(jnp.float32)[:, 0, :]   # (H, K)

    def step(window, u_t):                    # window (B, K-1, H)
        window = jnp.concatenate([window, u_t[:, None]], axis=1)
        return window[:, 1:], jnp.einsum("bkh,hk->bh", window, taps)
    tail, v = jax.lax.scan(step, jnp.zeros((h.shape[0], k - 1, hid),
                                           jnp.float32),
                           jnp.moveaxis(u, 1, 0))
    out = linear(cg * jnp.moveaxis(v, 0, 1), w[CONV + "out_proj.weight"][j])
    return out, tail


def route(cfg, s, bias, control=None):
    """``(weights (N, k), picked (N, k), margin (N,))`` of sigmoid scores ``s``
    (N, E): the margin is the relative gap between the last and the first
    left out of ``s + bias``, the quantity that picks."""
    k = cfg["num_experts_per_tok"]
    pick_by = s if control == "bias_dropped" else s + bias
    edge, picked = jax.lax.top_k(pick_by, k + 1)
    margin = (edge[:, k - 1] - edge[:, k]) / jnp.maximum(
        jnp.abs(edge[:, k - 1]), 1e-20)
    picked = picked[:, :k]
    weigh_by = s + bias if control == "bias_weighs" else s
    top = jnp.take_along_axis(weigh_by, picked, axis=-1)
    if cfg.get("norm_topk_prob", True) and control != "renorm_dropped":
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + TOPK_NORM_EPS)
    return top * cfg.get("routed_scaling_factor", 1.0), picked, margin


def _routed(cfg, w, j, g, control):
    b, s, hid = g.shape
    x = g.reshape(b * s, hid)
    scores = jax.nn.sigmoid(linear(x, w[FFN + "gate.weight"][j]))
    top, picked, margin = route(
        cfg, scores, w[FFN + "expert_bias"][j].astype(jnp.float32), control)
    weight_of = jnp.sum(jax.nn.one_hot(picked, cfg["num_experts"])
                        * top[..., None], axis=1)        # (N, E), 0 unpicked
    w1 = w[EXPERT + "w1.weight"][j].astype(jnp.float32)  # (E, I, H)
    w3 = w[EXPERT + "w3.weight"][j].astype(jnp.float32)
    w2 = w[EXPERT + "w2.weight"][j].astype(jnp.float32)  # (E, H, I)
    act = jax.nn.silu(jnp.einsum("nh,eih->nei", x, w1)) \
        * jnp.einsum("nh,eih->nei", x, w3)
    out = jnp.einsum("nei,ehi->nh", act * weight_of[..., None], w2)
    return out.reshape(b, s, hid), margin.reshape(b, s)


def layer(cfg, w, i, x, control=None):
    """Layer ``i`` over ``x`` (B, S, H): ``(x', margin (B, S), tail)``;
    ``margin`` is ``inf`` for a dense layer, ``tail`` None for attention."""
    eps, n_dense = cfg["norm_eps"], _dense(cfg)
    attn, conv = _layers(cfg, "full_attention"), _layers(cfg, "conv")
    h = rms_norm(x, w[L + "operator_norm.weight"][i], eps)
    tail = None
    if i in attn:
        x = x + _attention(cfg, w, attn.index(i), h, control)
    else:
        op, tail = _short_conv(cfg, w, conv.index(i), h, control)
        x = x + op
    g = rms_norm(x, w[L + "ffn_norm.weight"][i], eps)
    as_expert = control == "dense_as_expert" and i == n_dense - 1
    if i < n_dense and not as_expert:
        f = linear(jax.nn.silu(linear(g, w[FFN + "w1.weight"][i]))
                   * linear(g, w[FFN + "w3.weight"][i]),
                   w[FFN + "w2.weight"][i])
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    else:
        f, margin = _routed(cfg, w, max(i - n_dense, 0), g, control)
    return x + f, margin, tail


def final_hidden(cfg, w, ids, control=None):
    """``(N(x_L; embedding_norm) (B, S, H), margins (B, S), tails)``: what
    the head reads, per position the smallest routing margin over its expert
    layers, and every conv layer's tail after the last token."""
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    tails = []
    for i in range(cfg["num_hidden_layers"]):
        x, margin, tail = layer(cfg, w, i, x, control)
        margins = jnp.minimum(margins, margin)
        if tail is not None:
            tails.append(tail)
    return (rms_norm(x, w["model.embedding_norm.weight"], cfg["norm_eps"]),
            margins, tails)


def forward(cfg, w, ids, with_margins=False, control=None):
    """Float32 logits ``(B, S, vocab)``; with ``with_margins`` also ``(B, S)``
    float32, the smallest routing margin over a position's expert layers."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("the reference has the tied head only")
    x, margins, _ = final_hidden(cfg, w, ids, control)
    logits = linear(x, w["model.embed_tokens.weight"])
    return (logits, margins) if with_margins else logits


def final_tails(cfg, w, ids):
    """Every conv layer's ``u`` of the last ``K - 1`` tokens of ``ids``,
    ``(conv layers, B, K-1, hidden)`` float32, oldest first: what a served
    sequence's state slot is held to."""
    return jnp.stack(final_hidden(cfg, w, ids)[2])
