"""The plain reference of ``model_type`` ``granite`` (dense): the decoder of
``harness/reference.py`` (pre-norm residual blocks, float32 RMSNorm,
half-rotation rotary embedding, grouped-query softmax attention, SwiGLU) with
IBM's four multipliers, written from ``modeling_granite.py`` of transformers
as documented:

* ``embedding_multiplier`` scales the token embeddings;
* ``attention_multiplier`` IS the softmax scale (in place of
  ``head_dim ** -0.5``);
* ``residual_multiplier`` scales what each block adds to the residual stream
  (the attention's output and the MLP's);
* ``logits_scaling`` divides the logits.

A toy fixture of the benchmark's tests, found by its name: it shows that an
architecture arrives as a new file (``benchmark/README.md``, "A reference").
"""

import jax
import jax.numpy as jnp

from harness.reference import L, linear, rms_norm, rope, swiglu

ATTN = L + "self_attn."


def _head_dim(cfg):
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, inter = _head_dim(cfg), cfg["intermediate_size"]
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.norm.weight": {"shape": (hid,), "init": "norm"},
        L + "input_layernorm.weight": {"shape": (n_l, hid), "init": "norm"},
        L + "post_attention_layernorm.weight": {"shape": (n_l, hid),
                                                "init": "norm"},
    }
    for name, shape in {
            ATTN + "q_proj.weight": (n_l, nq * d, hid),
            ATTN + "k_proj.weight": (n_l, nkv * d, hid),
            ATTN + "v_proj.weight": (n_l, nkv * d, hid),
            ATTN + "o_proj.weight": (n_l, hid, nq * d),
            L + "mlp.gate_proj.weight": (n_l, inter, hid),
            L + "mlp.up_proj.weight": (n_l, inter, hid),
            L + "mlp.down_proj.weight": (n_l, hid, inter)}.items():
        table[name] = {"shape": shape, "init": "normal"}
    if not cfg.get("tie_word_embeddings"):
        table["lm_head.weight"] = {"shape": (vocab, hid), "init": "normal"}
    return table


def _attention(cfg, w, i, h):
    b, s, _ = h.shape
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    pos = jnp.arange(s)
    theta = float(cfg["rope_theta"])
    q = rope(linear(h, w[ATTN + "q_proj.weight"][i]).reshape(b, s, nq, d),
             pos, theta)
    k = rope(linear(h, w[ATTN + "k_proj.weight"][i]).reshape(b, s, nkv, d),
             pos, theta)
    v = linear(h, w[ATTN + "v_proj.weight"][i]).reshape(b, s, nkv, d)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["attention_multiplier"]
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return linear(out.reshape(b, s, nq * d), w[ATTN + "o_proj.weight"][i])


def forward(cfg, w, ids, with_margins=False):
    """Float32 logits ``(B, S, vocab)``; the margins of a dense model are
    ``inf`` everywhere (nothing is routed)."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    embed = w["model.embed_tokens.weight"]
    x = embed[ids].astype(jnp.float32) * cfg["embedding_multiplier"]
    for i in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, w[L + "input_layernorm.weight"][i], eps)
        x = x + res * _attention(cfg, w, i, h)
        h = rms_norm(x, w[L + "post_attention_layernorm.weight"][i], eps)
        x = x + res * swiglu(h, w[L + "mlp.gate_proj.weight"][i],
                             w[L + "mlp.up_proj.weight"][i],
                             w[L + "mlp.down_proj.weight"][i])
    x = rms_norm(x, w["model.norm.weight"], eps)
    head = embed if cfg.get("tie_word_embeddings") else w["lm_head.weight"]
    logits = linear(x, head) / cfg["logits_scaling"]
    if with_margins:
        return logits, jnp.full(ids.shape, jnp.inf, jnp.float32)
    return logits
