"""The plain reference of ``model_type`` ``KeyeVL2``
(Kwai-Keye/Keye-VL-2.0-30B-A3B, the language model on token ids): float32,
the whole sequence through every layer, an explicit top-k a query, no cache,
no kernel, no batching. Written from the equations of ISSUE 50 (the catalog
row's ``config`` read as mathematics; what the config does not say is marked
``[assumed]`` and listed in the configuration file) and from nothing of this
repository's ``modules/``. transformers 4.57.6 has no ``KeyeVL2`` class, so
``tests/test_reference_KeyeVL2.py`` holds ``forward`` to a second,
token-by-token writing of the same equations in NumPy float64.

``N(x; g) = x * rsqrt(mean x^2 + eps) * g``; every projection bias-free.
Layer ``l``, input ``x``, positions ``t`` (queries) and ``s <= t`` (cached):

    a = N(x; g_in)
    q = rope(N_head(W_q a)), k = rope(N_head(W_k a)), v = W_v a
                                     # 32 / 4 heads of 128; the per-head q / k
                                     # RMSNorm is Qwen3-MoE's [assumed]
    qI[t, j] = rope(W_Iq a_t)[j]     # 16 index heads of 64   [reads a: assumed]
    kI[s]    = rope(LayerNorm(W_Ik a_s))      # ONE head of 64 [LayerNorm and
                                     # rotary over all 64 lanes: assumed]
    w[t, j]  = (W_Iw a_t)[j]
    I[t, s]  = sum_j w[t, j] ReLU(qI[t, j] . kI[s])
    S_t      = the topk s <= t of largest I[t, s] (ties: the lower s; every
               s <= t while t < topk)
    h = x + W_o softmax_{s in S_t}(q_t k_s^T / sqrt(128)) v_s
                                     # 8 query heads a kv head, ONE S_t a token
    m = N(h; g_post)
    p = softmax(W_r m) over ALL router columns; top 8, renormalised
    y = sum_{e picked and held} p_e W_down^e (silu(W_gate^e m) * W_up^e m)
    x' = h + y
    logits = W_head N(x_L; g_f)            # untied

The constant positive scales the published description puts on ``w``
(``16^-1/2``, ``64^-1/2``) are left out: they cannot change a top-k.
``sa_config``'s ``q_chunk_size`` / ``kv_chunk_size`` are read as a kernel's
tile sizes with no effect on the result: selection is per token [assumed].
With text ids the three ``mrope_section`` parts carry one position and the
rotary is the ordinary one.

ONE CHIP'S SHARE. ``num_experts`` is the number of experts the weights HOLD.
Where the config also gives ``router_num_experts`` (the published count) and
``first_expert``, the router scores all ``router_num_experts`` and the sum
runs over the held experts ``first_expert .. first_expert + num_experts - 1``
only; the other chips' part is left out. Without the key every expert is
held.

``with_margins``: per position the smaller of its routing margin (the
relative gap between the last probability picked and the first left out) and
its SELECTION margin (the relative gap between the ``topk``-th index score
and the next, ``inf`` while everything is selected), the least over the
layers: where it is small the model's function jumps, and a bfloat16
evaluation may attend another token, or route to another expert, without
being wrong.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import jax
import jax.numpy as jnp

from harness.reference import EXPERT, L, linear, rms_norm, rope

ATTN = L + "self_attn."
INDEXER = ATTN + "indexer."
ROUTER = L + "mlp.gate.weight"

#: eps of the index key's LayerNorm [assumed: DeepSeek-V3.2-Exp's]
INDEX_NORM_EPS = 1e-6

#: faults a comparison against the served path must catch; the last two
#: compute the cached index key and the index scores ONE PRECISION BELOW the
#: stated one (bfloat16 keys, float32 scores)
CONTROLS = ("dense_attention", "no_relu", "no_head_weights", "half_topk",
            "k_not_normed", "keys_not_rotated", "keys_fp8", "scores_bf16")


def share(cfg):
    """``(experts the router scores, experts held, the first held)``."""
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
    sa = cfg["sa_config"]
    nj, dj = sa["indexer_num_heads"], sa["indexer_head_dim"]
    routed, held, _ = share(cfg)
    inter = cfg["moe_intermediate_size"]

    def normal(*shape):
        return {"shape": shape, "init": "normal"}

    def norm(*shape):
        return {"shape": shape, "init": "norm"}
    return {
        "model.embed_tokens.weight": normal(vocab, hid),
        "model.norm.weight": norm(hid),
        "lm_head.weight": normal(vocab, hid),
        L + "input_layernorm.weight": norm(n_l, hid),
        L + "post_attention_layernorm.weight": norm(n_l, hid),
        ATTN + "q_proj.weight": normal(n_l, nq * d, hid),
        ATTN + "k_proj.weight": normal(n_l, nkv * d, hid),
        ATTN + "v_proj.weight": normal(n_l, nkv * d, hid),
        ATTN + "o_proj.weight": normal(n_l, hid, nq * d),
        ATTN + "q_norm.weight": norm(n_l, d),
        ATTN + "k_norm.weight": norm(n_l, d),
        INDEXER + "wq.weight": normal(n_l, nj * dj, hid),
        INDEXER + "wk.weight": normal(n_l, dj, hid),
        INDEXER + "k_norm.weight": norm(n_l, dj),
        INDEXER + "k_norm.bias": normal(n_l, dj),
        INDEXER + "weights_proj.weight": normal(n_l, nj, hid),
        ROUTER: normal(n_l, routed, hid),
        EXPERT + "gate_proj.weight": normal(n_l, held, inter, hid),
        EXPERT + "up_proj.weight": normal(n_l, held, inter, hid),
        EXPERT + "down_proj.weight": normal(n_l, held, hid, inter),
    }


def layer_norm(x, weight, bias, eps=INDEX_NORM_EPS):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) \
        * weight.astype(jnp.float32) + bias.astype(jnp.float32)


def index_scores(cfg, w, i, a, control=None, rows=None):
    """``I[t, s]`` (B, queries, S) float32 of layer ``i`` from the normed
    input ``a``; entries with ``s > t`` are ``-inf``. ``rows`` = (lo, hi):
    the queries at positions ``lo .. hi - 1`` alone (a long sequence goes
    through a block of queries at a time); every key either way."""
    b, s, _ = a.shape
    lo, hi = rows or (0, s)
    sa = cfg["sa_config"]
    nj, dj = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta = float(cfg["rope_theta"])
    pos = jnp.arange(s)
    qi = rope(linear(a[:, lo:hi], w[INDEXER + "wq.weight"][i]
                     ).reshape(b, hi - lo, nj, dj), pos[lo:hi], theta)
    ki = linear(a, w[INDEXER + "wk.weight"][i])
    if control != "k_not_normed":
        ki = layer_norm(ki, w[INDEXER + "k_norm.weight"][i],
                        w[INDEXER + "k_norm.bias"][i])
    if control != "keys_not_rotated":
        ki = rope(ki[:, :, None, :], pos, theta)[:, :, 0]
    if control == "keys_fp8":
        ki = ki.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    dots = jnp.einsum("bqjd,bkd->bqjk", qi, ki)
    if control != "no_relu":
        dots = jax.nn.relu(dots)
    head_w = linear(a[:, lo:hi], w[INDEXER + "weights_proj.weight"][i])
    if control == "no_head_weights":
        head_w = jnp.ones_like(head_w)
    scores = jnp.einsum("bqjk,bqj->bqk", dots, head_w)
    if control == "scores_bf16":
        scores = scores.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.where((pos[lo:hi, None] >= pos[None, :])[None], scores,
                     -jnp.inf)


def select(cfg, scores, control=None, rows=None):
    """``(S (B, queries, S) bool, margin (B, queries))``: for each query the
    ``topk`` positions of largest score (``jax.lax.top_k``: ties to the
    lower position), all of its ``t + 1`` while that is at most ``topk``;
    the margin is the relative gap between the last score kept and the first
    left out, ``inf`` where nothing is left out."""
    b, n, s = scores.shape
    lo, hi = rows or (0, s)
    topk = cfg["sa_config"]["topk"]
    if control == "half_topk":
        topk = max(1, topk // 2)
    causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :]
    if control == "dense_attention" or topk >= s:
        return (jnp.broadcast_to(causal, scores.shape),
                jnp.full((b, n), jnp.inf, jnp.float32))
    vals, idx = jax.lax.top_k(scores, topk + 1)
    kept = jnp.zeros(scores.shape, bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(n)[None, :, None],
        idx[..., :topk]].set(True) & causal[None]
    last, nxt = vals[..., topk - 1], vals[..., topk]
    gap = (last - nxt) / jnp.maximum(
        jnp.maximum(jnp.abs(last), jnp.abs(nxt)), 1e-30)
    return kept, jnp.where(jnp.arange(lo, hi)[None, :] >= topk, gap, jnp.inf)


def attention(cfg, w, i, a, kept, rows=None):
    b, s, _ = a.shape
    lo, hi = rows or (0, s)
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(s)
    q = linear(a[:, lo:hi], w[ATTN + "q_proj.weight"][i]
               ).reshape(b, hi - lo, nq, d)
    k = linear(a, w[ATTN + "k_proj.weight"][i]).reshape(b, s, nkv, d)
    v = linear(a, w[ATTN + "v_proj.weight"][i]).reshape(b, s, nkv, d)
    q = rope(rms_norm(q, w[ATTN + "q_norm.weight"][i], eps), pos[lo:hi],
             theta)
    k = rope(rms_norm(k, w[ATTN + "k_norm.weight"][i], eps), pos, theta)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    scores = jnp.where(kept[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return linear(out.reshape(b, hi - lo, nq * d),
                  w[ATTN + "o_proj.weight"][i])


def experts(cfg, w, i, m):
    """``(y, margin)``: the held experts' part of the routed sum and, per
    position, the relative gap between the last probability picked and the
    first left out (over ALL router columns)."""
    routed, held, first = share(cfg)
    b, s, hid = m.shape
    k = cfg["num_experts_per_tok"]
    x = m.reshape(b * s, hid)
    probs = jax.nn.softmax(linear(x, w[ROUTER][i]), axis=-1)
    edge, top_e = jax.lax.top_k(probs, k + 1)
    top_p, top_e = edge[:, :k], top_e[:, :k]
    margin = ((edge[:, k - 1] - edge[:, k]) / edge[:, k - 1]).reshape(b, s)
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weight_of = jnp.sum(jax.nn.one_hot(top_e, routed) * top_p[..., None],
                        axis=1)[:, first:first + held]
    f32 = jnp.float32
    inter = jax.nn.silu(jnp.einsum(
        "nh,eih->nei", x, w[EXPERT + "gate_proj.weight"][i].astype(f32))) \
        * jnp.einsum("nh,eih->nei", x,
                     w[EXPERT + "up_proj.weight"][i].astype(f32))
    y = jnp.einsum("nei,ehi,ne->nh", inter,
                   w[EXPERT + "down_proj.weight"][i].astype(f32), weight_of)
    return y.reshape(b, s, hid), margin


def forward(cfg, w, ids, with_margins=False, control=None, block=None):
    """Next-token logits ``(B, S, vocab)`` in float32 for token ids ``(B, S)``
    under the published keys ``cfg``; with ``with_margins`` also, per
    position, the smallest routing or selection margin over its layers.
    ``block``: queries a pass of the indexer and the attention (a sequence
    of thousands of tokens: the (queries, S) scores of every head would not
    fit at once); the result does not depend on it."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    if cfg["sa_config"].get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the reference has ONE index key a token")
    eps = cfg["rms_norm_eps"]
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        a = rms_norm(x, w[L + "input_layernorm.weight"][i], eps)
        s = ids.shape[1]
        mixed, chosen = [], []
        for lo in range(0, s, block or s):
            rows = (lo, min(s, lo + (block or s)))
            kept, gap = select(
                cfg, index_scores(cfg, w, i, a, control, rows), control, rows)
            mixed.append(attention(cfg, w, i, a, kept, rows))
            chosen.append(gap)
        chosen = jnp.concatenate(chosen, axis=1)
        h = x + jnp.concatenate(mixed, axis=1)
        y, routed = experts(
            cfg, w, i,
            rms_norm(h, w[L + "post_attention_layernorm.weight"][i], eps))
        x = h + y
        margins = jnp.minimum(margins, jnp.minimum(chosen, routed))
    logits = linear(rms_norm(x, w["model.norm.weight"], eps),
                    w["lm_head.weight"])
    return (logits, margins) if with_margins else logits
