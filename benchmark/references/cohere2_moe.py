"""The plain reference of ``model_type`` ``cohere2_moe`` (CohereLabs
command-a-plus-05-2026, "Command A+"): float32, the whole sequence through
every layer, no cache, no kernel, no batching. Written from the equations of
ISSUE 56 (the catalog row's ``config`` read as mathematics, with
``transformers``' ``modeling_cohere2.py`` (4.57.6) for what the dense
``cohere2`` shares: the bias-free LayerNorm, the interleaved rotary, the
window mask, the tied head) and from nothing of this repository's
``modules/``. transformers 4.57.6 has no ``cohere2_moe`` class, so
``tests/test_reference_cohere2_moe.py`` holds ``forward`` to a second,
token-by-token writing of the same equations in NumPy float64.

``LN(x; g) = (x - mean x) * rsqrt(var x + layer_norm_eps) * g`` (no bias);
every projection bias-free, no q / k norm. Layer ``l``, input ``x``:

    n = LN(x; g_l)                                  # ONE norm feeds all three
    q, k, v = W_q n, W_k n, W_v n                   # heads of head_dim
    layer_types[l] == "sliding_attention":
        q, k rotated over INTERLEAVED pairs (2i, 2i+1), every lane, rope_theta
        mask(i, j) = j <= i and i - j < sliding_window
    layer_types[l] == "full_attention":
        NO rotary (NoPE); mask(i, j) = j <= i
    a = W_o softmax(q k^T / sqrt(head_dim) + mask) v
    s = sigmoid(W_r n)                          # every routed column, float32
    T = the num_experts_per_tok largest of s (ties to the lower index)
    g_e = s_e / sum_{j in T} s_j                    # norm_topk_prob
    r = sum_{e in T} g_e D_e(silu(G_e n) * U_e n)
    c = mean_i D'_i(silu(G'_i n) * U'_i n)      # num_shared_experts; [assumed]
    x' = x + a + r + c                              # use_parallel_block
    logits = E LN(x_L; g_f) * logit_scale           # tied embedding E

Departures from the published description, each ``[assumed]`` and listed in
the configuration file: ``intermediate_size`` is the width of ONE routed and
ONE shared expert; "shared experts averaged" is the MEAN of the shared
experts' outputs ADDED to the routed sum (not a mean over routed and
shared); no routing bias, groups or scaling factor (no such key); the tensor
names. ``first_k_dense_replace`` other than 0 is refused: the prefix dense
layer is not described. The vision tower is left out.

ONE CHIP'S SHARE. ``num_experts`` is the number of experts the weights HOLD.
Where the config also gives ``router_num_experts`` (the published count) and
``first_expert``, the router and the top k run over all of them, the weights
are renormalised over the k picked, held or not, and the sum runs over the
held experts ``first_expert .. first_expert + num_experts - 1`` only;
attention and the shared experts, every chip's alike, are whole on every
share. Without the key every routed expert is held. A sliced vocabulary is a
smaller ``vocab_size``.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import jax
import jax.numpy as jnp

from harness.reference import EXPERT, L, linear, swiglu

ATTN = L + "self_attn."
MLP = L + "mlp."
SHARED = MLP + "shared_experts.{e}."

#: faults a comparison against the served path must catch
CONTROLS = ("no_window", "window_plus_one", "rope_on_full", "rope_halves",
            "shared_sum", "no_shared", "not_renormalised", "softmax",
            "router_bf16", "sequential")

#: queries attended at once (the same arithmetic; a long sequence's scores
#: do not fit whole: 128 heads x 512 x 8192 float32 are 2.1 GB)
ATTEND_BLOCK = 512


def share(cfg):
    """``(routed experts the router scores, held, the first held)``."""
    held = cfg["num_experts"]
    routed = cfg.get("router_num_experts") or held
    first = cfg.get("first_expert") or 0
    if not 0 <= first <= routed - held:
        raise ValueError(f"experts {first}.. of {held} held, {routed} routed")
    return routed, held, first


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    routed, held, _ = share(cfg)
    inter, n_s = cfg["intermediate_size"], cfg["num_shared_experts"]

    def normal(*shape):
        return {"shape": shape, "init": "normal"}
    return {
        "model.embed_tokens.weight": normal(vocab, hid),
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        L + "input_layernorm.weight": {"shape": (n_l, hid), "init": "norm"},
        ATTN + "q_proj.weight": normal(n_l, nq * d, hid),
        ATTN + "k_proj.weight": normal(n_l, nkv * d, hid),
        ATTN + "v_proj.weight": normal(n_l, nkv * d, hid),
        ATTN + "o_proj.weight": normal(n_l, hid, nq * d),
        MLP + "gate.weight": normal(n_l, routed, hid),
        EXPERT + "gate_proj.weight": normal(n_l, held, inter, hid),
        EXPERT + "up_proj.weight": normal(n_l, held, inter, hid),
        EXPERT + "down_proj.weight": normal(n_l, held, hid, inter),
        SHARED + "gate_proj.weight": normal(n_l, n_s, inter, hid),
        SHARED + "up_proj.weight": normal(n_l, n_s, inter, hid),
        SHARED + "down_proj.weight": normal(n_l, n_s, hid, inter),
    }


def layer_norm(x, weight, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def rotary(x, positions, theta, halves=False):
    """``x`` (B, S, heads, D) rotated over every lane: pair ``i`` is lanes
    ``(2i, 2i+1)`` at the angle ``position * theta^(-2i / D)``
    (``rope_gptj``); ``halves`` (a control) pairs lane ``i`` with ``i + D /
    2`` instead."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if halves:
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attend(q, k, v, reach):
    """Causal softmax attention of query heads ``q`` (B, S, kv heads, group,
    D) over keys and values (B, S, kv heads, D), token ``i`` at position
    ``i``: a query sees the keys at or before it and, where ``reach`` is not
    None, fewer than ``reach`` positions back. The queries go a block at a
    time, each over the keys its block can see."""
    out = []
    for lo in range(0, q.shape[1], ATTEND_BLOCK):
        hi = min(lo + ATTEND_BLOCK, q.shape[1])
        first = 0 if reach is None else max(0, lo - reach + 1)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q[:, lo:hi],
                            k[:, first:hi]) * (q.shape[-1] ** -0.5)
        dist = jnp.arange(lo, hi)[:, None] - jnp.arange(first, hi)[None, :]
        seen = dist >= 0
        if reach is not None:
            seen = seen & (dist < reach)
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd",
                              jax.nn.softmax(scores, axis=-1),
                              v[:, first:hi]))
    return jnp.concatenate(out, axis=1)


def attention(cfg, w, i, n, control=None):
    b, s, _ = n.shape
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    kind = cfg["layer_types"][i]
    if kind not in ("sliding_attention", "full_attention"):
        raise ValueError(f"layer_types[{i}] is {kind!r}")
    windowed = kind == "sliding_attention"
    q = linear(n, w[ATTN + "q_proj.weight"][i]).reshape(b, s, nq, d)
    k = linear(n, w[ATTN + "k_proj.weight"][i]).reshape(b, s, nkv, d)
    v = linear(n, w[ATTN + "v_proj.weight"][i]).reshape(b, s, nkv, d)
    pos = jnp.arange(s)
    if windowed or control == "rope_on_full":
        theta, halves = float(cfg["rope_theta"]), control == "rope_halves"
        q, k = rotary(q, pos, theta, halves), rotary(k, pos, theta, halves)
    reach = None
    if windowed and control != "no_window":
        reach = cfg["sliding_window"] + (control == "window_plus_one")
    # query head h reads kv head h // (nq / nkv)
    out = attend(q.reshape(b, s, nkv, nq // nkv, d), k, v, reach)
    return linear(out.reshape(b, s, nq * d), w[ATTN + "o_proj.weight"][i])


def routing(cfg, w, i, n, control=None):
    """``(weights (B, S, k), columns (B, S, k), margin (B, S))``: sigmoid
    scores of every routed column in float32, the k largest, renormalised
    over the k. The margin is the gap between the last score kept and the
    first dropped, as a share of the last kept."""
    k = cfg["num_experts_per_tok"]
    if cfg.get("expert_selection_fn", "sigmoid") != "sigmoid":
        raise ValueError("the reference has the sigmoid router only")
    gate = w[MLP + "gate.weight"][i]
    if control == "router_bf16":
        logits = linear(n.astype(jnp.bfloat16).astype(jnp.float32), gate)
        logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        logits = linear(n, gate)
    scores = (jax.nn.softmax(logits, axis=-1) if control == "softmax"
              else jax.nn.sigmoid(logits))
    edge, idx = jax.lax.top_k(scores, k + 1)
    margin = (edge[..., k - 1] - edge[..., k]) / edge[..., k - 1]
    top, idx = edge[..., :k], idx[..., :k]
    if cfg.get("norm_topk_prob", True) and control != "not_renormalised":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top, idx, margin


def _each_expert(n, w, names, i, weight):
    """``sum_e weight[e] * D_e(silu(G_e n) * U_e n)`` over the experts of
    layer ``i`` under the stacked ``names`` + ``{gate,up,down}_proj.weight``
    (L, E, ...), one expert after another (a loop over the expert's index,
    each read out of the stack where it lies: one expert's float32 matrices
    live at a time); ``weight`` (E, B, S) is each token's weight on each
    expert."""
    gate, up, down = (w[names + proj + "_proj.weight"]
                      for proj in ("gate", "up", "down"))

    def add(e, total):
        return total + weight[e][..., None] * swiglu(
            n, gate[i, e], up[i, e], down[i, e])
    return jax.lax.fori_loop(0, weight.shape[0], add, jnp.zeros_like(n))


def routed_experts(cfg, w, i, n, control=None):
    """``(r, margin)``: the held experts' part of the routed sum, one expert
    after another."""
    _, held, first = share(cfg)
    top, idx, margin = routing(cfg, w, i, n, control)
    weight = jnp.stack([jnp.sum(jnp.where(idx == first + e, top, 0.0), axis=-1)
                        for e in range(held)])
    return _each_expert(n, w, EXPERT, i, weight), margin


def shared_experts(cfg, w, i, n, control=None):
    """``c``: the ``num_shared_experts`` shared experts, each computed on its
    own, their outputs AVERAGED."""
    count = cfg["num_shared_experts"]
    if cfg.get("shared_expert_combination_strategy", "average") != "average":
        raise ValueError("the reference averages the shared experts only")
    if not count or control == "no_shared":
        return jnp.zeros_like(n)
    total = _each_expert(n, w, SHARED, i,
                         jnp.ones((count,) + n.shape[:-1], jnp.float32))
    return total if control == "shared_sum" else total / count


def layer(cfg, w, i, x, control=None):
    """``(x', margin)`` of layer ``i``: the parallel block."""
    eps = cfg["layer_norm_eps"]
    n = layer_norm(x, w[L + "input_layernorm.weight"][i], eps)
    a = attention(cfg, w, i, n, control)
    # the control: a sequential block, the experts reading the stream
    # behind attention through the same norm
    m = (layer_norm(x + a, w[L + "input_layernorm.weight"][i], eps)
         if control == "sequential" else n)
    r, margin = routed_experts(cfg, w, i, m, control)
    return x + a + r + shared_experts(cfg, w, i, m, control), margin


def final_hidden(cfg, w, ids, control=None):
    """``(LN(x_L; g_f) (B, S, hidden), margins (B, S))``: what the head
    reads, and per position the smallest routing margin over its layers."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    if cfg.get("first_k_dense_replace"):
        raise ValueError("the reference has no prefix dense layer "
                         "(first_k_dense_replace must be 0)")
    if not cfg.get("use_parallel_block", True) or cfg.get("use_qk_norm"):
        raise ValueError("the reference has the parallel block without q / "
                         "k norms only")
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x, margin = layer(cfg, w, i, x, control)
        margins = jnp.minimum(margins, margin)
    return layer_norm(x, w["model.norm.weight"], cfg["layer_norm_eps"]), \
        margins


def forward(cfg, w, ids, with_margins=False, control=None):
    """Next-token logits ``(B, S, vocab)`` in float32 for token ids ``(B, S)``
    under the published keys ``cfg``; with ``with_margins`` also, per
    position, the smallest routing margin over its layers."""
    hidden, margins = final_hidden(cfg, w, ids, control)
    logits = linear(hidden, w["model.embed_tokens.weight"]) \
        * float(cfg.get("logit_scale", 1.0))
    return (logits, margins) if with_margins else logits
