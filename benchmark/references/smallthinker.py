"""The plain reference of ``model_type`` ``smallthinker``
(PowerInfer/SmallThinker-21BA3B-Instruct): float32, the whole sequence
through every layer, no cache, no kernel, no batching. Written from the
equations of ISSUE 43 (the catalog row's ``config`` read as mathematics; the
three places where its ``described_as`` is the only witness are marked
``[assumed]`` and listed in the configuration file) and from nothing of this
repository's ``modules/``. transformers 4.57.6 has no ``smallthinker`` class,
so ``tests/test_reference_smallthinker.py`` holds ``forward`` to a second,
token-by-token writing of the same equations in NumPy float64.

``N(x; g) = x * rsqrt(mean x^2 + eps) * g``; every projection bias-free, no
q / k norm [assumed]. Layer ``l``, input ``x``, ``w(l) =
sliding_window_layout[l]``, ``r(l) = rope_layout[l]``:

    a = N(x; g_in)
    s = W_r a                        # router logits: the ATTENTION's input [assumed]
    q, k, v = W_q a, W_k a, W_v a    # heads of head_dim; 7 query heads a kv head
    if r(l): q, k = rope(q, k)       # rope_theta, half-split pairs, every lane;
                                     # r(l) = 0: NO positional signal
    mask(i, j) = j <= i and (w(l) == 0 or i - j < sliding_window_size)
    h = x + W_o softmax(q k^T / sqrt(head_dim) + mask) v
    m = N(h; g_post)
    S = top-k of s;  p = softmax(s[S])     # = softmax over all, renormalised over S
    y = sum_{e in S} p_e W_down^e (relu(W_gate^e m) * W_up^e m)        # [assumed]
    x' = h + y
    logits = W_head N(x_L; g_f)            # untied

Every layer's MLP is the routed block; only the primary experts exist.

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import jax
import jax.numpy as jnp

from harness.reference import L, linear, rms_norm, rope

ATTN = L + "self_attn."
MOE = L + "block_sparse_moe."
EXPERT = MOE + "experts.{e}."

#: faults a comparison against the served path must catch
CONTROLS = ("no_window", "rope_on_global", "window_plus_one",
            "router_post_attn", "silu_gate", "not_renormalised")


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    n_e, inter = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    return {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        "lm_head.weight": {"shape": (vocab, hid), "init": "normal"},
        L + "input_layernorm.weight": {"shape": (n_l, hid), "init": "norm"},
        L + "post_attention_layernorm.weight": {"shape": (n_l, hid),
                                                "init": "norm"},
        ATTN + "q_proj.weight": {"shape": (n_l, nq * d, hid),
                                 "init": "normal"},
        ATTN + "k_proj.weight": {"shape": (n_l, nkv * d, hid),
                                 "init": "normal"},
        ATTN + "v_proj.weight": {"shape": (n_l, nkv * d, hid),
                                 "init": "normal"},
        ATTN + "o_proj.weight": {"shape": (n_l, hid, nq * d),
                                 "init": "normal"},
        MOE + "primary_router.weight": {"shape": (n_l, n_e, hid),
                                        "init": "normal"},
        EXPERT + "gate.weight": {"shape": (n_l, n_e, inter, hid),
                                 "init": "normal"},
        EXPERT + "up.weight": {"shape": (n_l, n_e, inter, hid),
                               "init": "normal"},
        EXPERT + "down.weight": {"shape": (n_l, n_e, hid, inter),
                                 "init": "normal"},
    }


def layouts(cfg):
    """``(window?, rotary?)`` a layer, as the config's two layouts give them
    (``rope_layout`` absent = the window layout)."""
    window = [int(x) for x in cfg["sliding_window_layout"]]
    rotary = [int(x) for x in cfg.get("rope_layout") or window]
    if len(window) != cfg["num_hidden_layers"] or len(rotary) != len(window):
        raise ValueError("the layouts give one entry a layer")
    return window, rotary


def attend(q, k, v, q_pos, k_pos, reach):
    """Softmax attention of query heads ``q`` (B, Q, heads, D) at positions
    ``q_pos`` over keys and values (B, K, heads, D) at ``k_pos``: a query
    sees the keys at or before it and, where ``reach`` is not None, fewer
    than ``reach`` positions back."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    dist = q_pos[:, None] - k_pos[None, :]
    seen = dist >= 0
    if reach is not None:
        seen = seen & (dist < reach)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def attention(cfg, w, i, a, windowed, rotary, control=None):
    b, s, _ = a.shape
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = linear(a, w[ATTN + "q_proj.weight"][i]).reshape(b, s, nq, d)
    k = linear(a, w[ATTN + "k_proj.weight"][i]).reshape(b, s, nkv, d)
    v = linear(a, w[ATTN + "v_proj.weight"][i]).reshape(b, s, nkv, d)
    pos = jnp.arange(s)
    if rotary or control == "rope_on_global":
        q = rope(q, pos, float(cfg["rope_theta"]))
        k = rope(k, pos, float(cfg["rope_theta"]))
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    reach = None
    if windowed and control != "no_window":
        reach = cfg["sliding_window_size"] + (control == "window_plus_one")
    out = attend(q, k, v, pos, pos, reach)
    return linear(out.reshape(b, s, nq * d), w[ATTN + "o_proj.weight"][i])


def experts(cfg, w, i, a, m, control=None):
    """The routed block of layer ``i``: routing from ``a`` (the attention's
    normed input), experts on ``m`` (the post-attention norm). Returns the
    output and, per position, the relative gap between the last probability
    picked and the first left out (over all experts)."""
    b, s, hid = m.shape
    k, n_e = cfg["moe_num_active_primary_experts"], cfg["moe_num_primary_experts"]
    x = m.reshape(b * s, hid)
    read = m if control == "router_post_attn" else a
    logits = linear(read.reshape(b * s, hid),
                    w[MOE + "primary_router.weight"][i])
    top_s, top_e = jax.lax.top_k(logits, k)
    if not cfg.get("moe_primary_router_apply_softmax", True):
        raise ValueError("the reference has the softmax router only")
    probs = jax.nn.softmax(logits, axis=-1)
    if cfg.get("norm_topk_prob", True) and control != "not_renormalised":
        top_p = jax.nn.softmax(top_s, axis=-1)
    else:
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    edge = jax.lax.top_k(probs, k + 1)[0]
    margin = ((edge[:, k - 1] - edge[:, k]) / edge[:, k - 1]).reshape(b, s)
    weight_of = jnp.sum(jax.nn.one_hot(top_e, n_e) * top_p[..., None], axis=1)
    gate = w[EXPERT + "gate.weight"][i].astype(jnp.float32)      # (E, I, H)
    up = w[EXPERT + "up.weight"][i].astype(jnp.float32)
    down = w[EXPERT + "down.weight"][i].astype(jnp.float32)      # (E, H, I)
    act = jax.nn.silu if control == "silu_gate" else jax.nn.relu
    inter = act(jnp.einsum("nh,eih->nei", x, gate)) \
        * jnp.einsum("nh,eih->nei", x, up)
    every = jnp.einsum("nei,ehi->neh", inter, down)
    return (jnp.einsum("ne,neh->nh", weight_of, every).reshape(b, s, hid),
            margin)


def forward(cfg, w, ids, with_margins=False, control=None):
    """Next-token logits ``(B, S, vocab)`` in float32 for token ids ``(B, S)``
    under the published keys ``cfg``; with ``with_margins`` also, per
    position, the smallest routing margin over its layers."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    eps = cfg["rms_norm_eps"]
    window, rotary = layouts(cfg)
    x = w["model.embed_tokens.weight"][ids].astype(jnp.float32)
    margins = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        a = rms_norm(x, w[L + "input_layernorm.weight"][i], eps)
        h = x + attention(cfg, w, i, a, window[i], rotary[i], control)
        m = rms_norm(h, w[L + "post_attention_layernorm.weight"][i], eps)
        y, margin = experts(cfg, w, i, a, m, control)
        x = h + y
        margins = jnp.minimum(margins, margin)
    logits = linear(rms_norm(x, w["model.norm.weight"], eps),
                    w["lm_head.weight"])
    return (logits, margins) if with_margins else logits
