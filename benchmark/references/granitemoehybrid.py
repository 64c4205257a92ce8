"""The plain reference of ``model_type`` ``granitemoehybrid`` (the dense
members: ``num_local_experts`` 0), written from ``modeling_granitemoehybrid.py``
of transformers as documented and from the Mamba-2 paper's recurrence, not
from this repository's ``modules/ssm.py``: float32, one token after another
through the state recurrence (no chunked form, no cache), the whole sequence
through every layer.

Every layer: ``h = x + r * mixer(rmsnorm(x)); x' = h + r * mlp(rmsnorm(h))``
with ``r = residual_multiplier`` and ``mlp(u) = W_out (silu(g) * v)``,
``[g | v] = W_in u`` (``shared_mlp``). ``x0 = embedding_multiplier * E[ids]``,
logits ``= E^T rmsnorm(x_L) / logits_scaling`` (tied) . A layer's mixer is, by
``layer_types``:

* ``attention``: grouped-query softmax attention with ``attention_multiplier``
  as the softmax scale and no positional embedding
  (``position_embedding_type`` ``nope``; ``rope`` applies the half-rotation
  form of ``harness/reference.py``);
* ``mamba``: Mamba-2. ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC) +
  b)`` with a depthwise causal convolution of width ``mamba_d_conv``; split
  into ``x`` (heads x head_dim), ``B``, ``C`` (groups x state); ``dt =
  softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head and step ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; then
  ``y = w * rmsnorm(y * silu(z))`` over the whole inner width and ``W_out y``.
"""

import jax
import jax.numpy as jnp

from harness.reference import L, linear, rms_norm, rope

ATTN = L + "self_attn."
MAMBA = L + "mamba."
MLP = L + "shared_mlp."


def _geometry(cfg):
    hid = cfg["hidden_size"]
    nh, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    d_inner = int(cfg["mamba_expand"] * hid)
    if d_inner != nh * hd:
        raise ValueError("mamba_expand * hidden_size must be mamba_n_heads * "
                         "mamba_d_head")
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d_inner, nh, hd, gn


def _layers(cfg, kind):
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    return [i for i, t in enumerate(types) if t == kind]


def weight_shapes(cfg):
    if cfg.get("num_local_experts"):
        raise ValueError("the reference covers num_local_experts == 0 only")
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // nq
    inter = cfg["shared_intermediate_size"]
    d_inner, nh, _, gn = _geometry(cfg)
    conv_dim, k = d_inner + 2 * gn, cfg["mamba_d_conv"]
    attn, mamba = _layers(cfg, "attention"), _layers(cfg, "mamba")
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        L + "input_layernorm.weight": {"shape": (n_l, hid), "init": "norm"},
        L + "post_attention_layernorm.weight": {"shape": (n_l, hid),
                                                "init": "norm"},
        MLP + "input_linear.weight": {"shape": (n_l, 2 * inter, hid),
                                      "init": "normal"},
        MLP + "output_linear.weight": {"shape": (n_l, hid, inter),
                                       "init": "normal"},
    }
    for name, shape in {
            ATTN + "q_proj.weight": (nq * d, hid),
            ATTN + "k_proj.weight": (nkv * d, hid),
            ATTN + "v_proj.weight": (nkv * d, hid),
            ATTN + "o_proj.weight": (hid, nq * d)}.items():
        table[name] = {"shape": (len(attn),) + shape, "init": "normal",
                       "layers": attn}
    # the mixer's own parameters as torch and HF initialise them: the
    # depthwise convolution as nn.Conv1d does (uniform within 1/sqrt(width):
    # drawn N(0, 0.02) it would shrink x, B and C fifty-fold and leave the
    # state a thousandth of the D * x path, where a broken carry cannot
    # show), A = 1..16, dt = 1e-3..1e-1 (HF's time_step_min/max), D = 1
    bound = float(k) ** -0.5
    for name, shape, init in (
            ("in_proj.weight", (2 * d_inner + 2 * gn + nh, hid), "normal"),
            ("conv1d.weight", (conv_dim, 1, k), ["uniform", -bound, bound]),
            ("conv1d.bias", (conv_dim,), ["uniform", -bound, bound]),
            ("dt_bias", (nh,), ["uniform", -6.9, -2.25]),
            ("A_log", (nh,), ["uniform", 0.0, 2.77]),
            ("D", (nh,), "ones"),
            ("norm.weight", (d_inner,), "norm"),
            ("out_proj.weight", (hid, d_inner), "normal")):
        table[MAMBA + name] = {"shape": (len(mamba),) + shape, "init": init,
                               "layers": mamba}
    if not cfg.get("tie_word_embeddings", True):
        table["lm_head.weight"] = {"shape": (vocab, hid), "init": "normal"}
    return table


def _attention(cfg, w, j, h):
    b, s, hid = h.shape
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // nq
    pos = jnp.arange(s)
    q = linear(h, w[ATTN + "q_proj.weight"][j]).reshape(b, s, nq, d)
    k = linear(h, w[ATTN + "k_proj.weight"][j]).reshape(b, s, nkv, d)
    v = linear(h, w[ATTN + "v_proj.weight"][j]).reshape(b, s, nkv, d)
    if cfg.get("position_embedding_type", "nope") == "rope":
        q = rope(q, pos, float(cfg["rope_theta"]))
        k = rope(k, pos, float(cfg["rope_theta"]))
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["attention_multiplier"]
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return linear(out.reshape(b, s, nq * d), w[ATTN + "o_proj.weight"][j])


def _mamba(cfg, w, j, u):
    b, s, _ = u.shape
    d_inner, nh, hd, gn = _geometry(cfg)
    g, n, k = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    f32 = jnp.float32
    zxbcdt = linear(u, w[MAMBA + "in_proj.weight"][j])
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    conv_w = w[MAMBA + "conv1d.weight"][j].astype(f32)[:, 0, :]   # (C, K)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * conv_w[:, i] for i in range(k))
    xbc = jax.nn.silu(conv + w[MAMBA + "conv1d.bias"][j].astype(f32))
    x = xbc[..., :d_inner].reshape(b, s, nh, hd)
    bm = jnp.repeat(xbc[..., d_inner:d_inner + gn].reshape(b, s, g, n),
                    nh // g, axis=2)
    cm = jnp.repeat(xbc[..., d_inner + gn:].reshape(b, s, g, n),
                    nh // g, axis=2)
    dt = jax.nn.softplus(dt + w[MAMBA + "dt_bias"][j].astype(f32))  # (b,s,nh)
    a = -jnp.exp(w[MAMBA + "A_log"][j].astype(f32))                 # (nh,)
    d_skip = w[MAMBA + "D"][j].astype(f32)

    def step(state, t):                         # state (b, nh, hd, n)
        x_t, b_t, c_t, dt_t = t
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.einsum("bhdn,bhn->bhd", state, c_t) \
            + d_skip[:, None] * x_t
        return state, y_t

    def time_first(t):
        return jnp.moveaxis(t, 1, 0)
    last, y = jax.lax.scan(step, jnp.zeros((b, nh, hd, n), f32),
                           (time_first(x), time_first(bm), time_first(cm),
                            time_first(dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d_inner)
    y = rms_norm(y * jax.nn.silu(z), w[MAMBA + "norm.weight"][j],
                 cfg["rms_norm_eps"])
    return linear(y, w[MAMBA + "out_proj.weight"][j]), last


def _walk(cfg, w, ids):
    """``(logits (B, S, vocab), states)``: ``states`` is each Mamba-2
    layer's ``S`` after the last token, ``(B, heads, head_dim, state)``."""
    if cfg.get("num_local_experts"):
        raise ValueError("the reference covers num_local_experts == 0 only")
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    inter = cfg["shared_intermediate_size"]
    attn, mamba = _layers(cfg, "attention"), _layers(cfg, "mamba")
    embed = w["model.embed_tokens.weight"]
    x = embed[ids].astype(jnp.float32) * cfg["embedding_multiplier"]
    states = []
    for i in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, w[L + "input_layernorm.weight"][i], eps)
        if i in attn:
            mixed = _attention(cfg, w, attn.index(i), h)
        else:
            mixed, last = _mamba(cfg, w, mamba.index(i), h)
            states.append(last)
        x = x + res * mixed
        h = rms_norm(x, w[L + "post_attention_layernorm.weight"][i], eps)
        gv = linear(h, w[MLP + "input_linear.weight"][i])
        x = x + res * linear(jax.nn.silu(gv[..., :inter]) * gv[..., inter:],
                             w[MLP + "output_linear.weight"][i])
    x = rms_norm(x, w["model.norm.weight"], eps)
    head = embed if cfg.get("tie_word_embeddings", True) \
        else w["lm_head.weight"]
    return linear(x, head) / cfg["logits_scaling"], states


def forward(cfg, w, ids, with_margins=False):
    """Float32 logits ``(B, S, vocab)``; nothing is routed, so the margins
    are ``inf`` everywhere."""
    logits, _ = _walk(cfg, w, ids)
    if with_margins:
        return logits, jnp.full(ids.shape, jnp.inf, jnp.float32)
    return logits


def final_states(cfg, w, ids):
    """The recurrent state every Mamba-2 layer holds after the last token of
    ``ids``, ``(mamba layers, B, heads, head_dim, state)`` in float32: what
    a served sequence's state slot is held to (the logits of a short run
    cannot tell the precision the state is carried in; the state can)."""
    return jnp.stack(_walk(cfg, w, ids)[1])
