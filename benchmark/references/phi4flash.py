"""The plain reference of ``model_type`` ``phi4flash``
(microsoft/Phi-4-mini-flash-reasoning; arXiv:2507.06607, a
decoder-hybrid-decoder: SambaY with Differential Attention): float32, the
whole sequence through every layer, no cache, no kernel, no batching, the
selective scan one token after another. Written from the equations of ISSUE
54 (the catalog row's ``config`` read as mathematics and the published
modeling file as recalled: what the row does not carry is ``[assumed]`` here
and listed in the configuration file) and from nothing of this repository's
``modules/``. transformers 4.57.6 has no ``phi4flash`` class, so
``tests/test_reference_phi4flash.py`` holds the pieces to second writings.

``LN(x; g, b)`` is ``nn.LayerNorm`` at ``layer_norm_eps``; there is NO
positional signal anywhere. Layer ``l`` of ``N``, ``half = N / 2``:

    h  = x + T_l(LN(x; g_in, b_in))
    x' = h + W_down (silu(g) * u),  [g; u] = W_gate_up LN(h; g_post, b_post)

and the temporal block ``T_l`` by :func:`layer_kinds`:

* ``mamba`` (``l`` even, ``l <= half``), Mamba-1: ``[u; z] = W_in a``;
  ``u' = silu(conv(u) + b)`` (depthwise, causal, ``d_conv`` wide);
  ``[r; B; C] = W_x u'`` (``dt_rank + d_state + d_state``); ``dt =
  softplus(W_dt r + b_dt)``; ``A = -exp(A_log)`` (``d_inner x d_state``);
  ``S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * u'_t) (x) B_t``; ``y_t = S_t C_t
  + D * u'_t``; ``T = W_out (y * silu(z))``. Layer ``half`` also hands ``m =
  y`` (before the gate) to the Gated Memory Units of the same token.
* ``window`` (``l`` odd, ``l < half``) and ``full`` (``l = half + 1``):
  DIFFERENTIAL attention, causal, a window layer's query at ``t`` seeing keys
  ``t - sliding_window + 1 .. t``. ``[q; k; v] = W_qkv a + b``; heads of
  ``d = hidden / heads`` pair up by NEIGHBOURS: differential head ``j`` has
  ``q1 = q[2j]``, ``q2 = q[2j + 1]`` and, with ``g`` its kv pair (``j //
  (heads / kv heads)``), ``k1 = k[2g]``, ``k2 = k[2g + 1]``, ``v1 = v[2g]``,
  ``v2 = v[2g + 1]``. Four plain softmax attentions at scale ``d ** -0.5``:
  ``A1 = [att(q1, k1, v1); att(q1, k1, v2)]``, ``A2 = [att(q2, k2, v1);
  att(q2, k2, v2)]`` (``2 d`` wide); ``lam = exp(lq1 . lk1) - exp(lq2 . lk2)
  + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_j = RMSNorm(A1 -
  lam A2; g_sub) * (1 - lam_init)``; ``T = W_o [o_0 ..] + b_o``.
* ``cross`` (``l`` odd, ``l >= half + 2``): the same differential attention
  with its own ``W_q``, ``lam``, ``g_sub``, ``W_o`` and NO key or value of its
  own: ``k``, ``v`` are layer ``half + 1``'s, under the full causal mask.
* ``gmu`` (``l`` even, ``l >= half + 2``), a Gated Memory Unit: ``T = W_2 (m
  * silu(W_1 a))``, ``m`` layer ``half``'s ``y`` of this token.

``logits = E LN(x_N; g_f, b_f)`` (tied).

``control``: a deliberate fault, by name (:data:`CONTROLS`), that the
comparison with the served path must catch; the harness never sets one.
"""

import math

import jax
import jax.numpy as jnp

from harness.reference import linear

#: the table's keys must differ where a published name is shared by layers of
#: different kinds and shapes (an attention layer's and a cross layer's
#: ``attn.Wqkv.weight``; a Mamba layer's and a GMU's ``attn.in_proj.weight``;
#: every kind's ``attn.out_proj.weight``), and the names they FORMAT to must
#: not: this field formats to nothing (``weights.HfView`` formats a key with
#: ``i`` and ``e``)
_SAME = "{e!s:.0}"
L = "model.layers.{i}."
KINDS = ("mamba", "window", "full", "cross", "gmu")
#: a kind's tensors under the published prefix ``model.layers.{i}.attn.``
#: (the published layer holds its temporal block as ``attn`` whatever it is)
BLOCK = {"attn": L + "attn.", "mamba": L + _SAME + "attn.",
         "cross": L + _SAME * 2 + "attn.", "gmu": L + _SAME * 3 + "attn."}
MLP = L + "mlp."
DIFF = "inner_cross_attn."

#: faults a comparison against the served path must catch
CONTROLS = ("no_lambda", "lambda_init_only", "m_after_gate",
            "cross_reads_window_layer", "pair_by_halves", "no_window",
            "window_plus_one")


def layer_kinds(cfg):
    """The temporal block of every layer, from ``num_hidden_layers`` and
    ``mb_per_layer`` [assumed: the published index rules]."""
    n = cfg["num_hidden_layers"]
    if cfg.get("mb_per_layer", 2) != 2 or n % 4 or n < 4:
        raise ValueError("the index rules are written for mb_per_layer 2 "
                         "and a depth that is a multiple of four")
    half = n // 2

    def kind(l):
        if l <= half:
            return "window" if l % 2 else "mamba"
        if l == half + 1:
            return "full"
        return "cross" if l % 2 else "gmu"
    return [kind(l) for l in range(n)]


def geometry(cfg):
    """``(d_inner, d_state, d_conv, dt_rank)`` of a Mamba-1 block [assumed:
    the published configuration class's defaults, Mamba-1's own]."""
    hid = cfg["hidden_size"]
    return (int(cfg.get("mamba_expand", 2) * hid),
            int(cfg.get("mamba_d_state", 16)), int(cfg.get("mamba_d_conv", 4)),
            int(cfg.get("mamba_dt_rank") or math.ceil(hid / 16)))


def weight_shapes(cfg):
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // nq
    inter = cfg["intermediate_size"]
    d_inner, n, k, dt_rank = geometry(cfg)
    kinds = layer_kinds(cfg)
    at = {kind: [i for i, x in enumerate(kinds) if x == kind]
          for kind in KINDS}
    table = {
        "model.embed_tokens.weight": {"shape": (vocab, hid), "init": "normal"},
        "model.final_layernorm.weight": {"shape": (hid,), "init": "norm"},
        "model.final_layernorm.bias": {"shape": (hid,), "init": "normal"},
        L + "input_layernorm.weight": {"shape": (n_l, hid), "init": "norm"},
        L + "input_layernorm.bias": {"shape": (n_l, hid), "init": "normal"},
        L + "post_attention_layernorm.weight": {"shape": (n_l, hid),
                                                "init": "norm"},
        L + "post_attention_layernorm.bias": {"shape": (n_l, hid),
                                              "init": "normal"},
        MLP + "gate_up_proj.weight": {"shape": (n_l, 2 * inter, hid),
                                      "init": "normal"},
        MLP + "down_proj.weight": {"shape": (n_l, hid, inter),
                                   "init": "normal"},
    }

    def add(block, layers, entries):
        for name, shape, init in entries:
            table[BLOCK[block] + name] = {
                "shape": (len(layers),) + shape, "init": init,
                "layers": layers}

    # lam's vectors: published N(0, 0.1), which leaves lam = lam_init +- 0.1:
    # a dropped learned term would hide, and at depth (lam_init -> 0.8) some
    # seed's lam lands on 1, where A1 - lam A2 cancels and the norm behind it
    # blows a bf16 rounding up fourteen-fold (read on the CPU backend, PR
    # 54). Drawn with a sign instead: lq1 . lk1 = -0.16 and lq2 . lk2 = +0.08
    # at 64 lanes, so lam = lam_init - 0.23 whatever the seed: away from
    # lam_init, from 0 (0.13 at layer 1) and from 1 (0.57 at layer 31) alike
    diff = [(DIFF + "lambda_" + v, (d,), ["uniform", lo, hi])
            for v, lo, hi in (("q1", 0.03, 0.07), ("k1", -0.07, -0.03),
                              ("q2", 0.02, 0.05), ("k2", 0.02, 0.05))] \
        + [(DIFF + "subln.weight", (2 * d,), "norm")]
    out = [("out_proj.weight", (hid, nq * d), "normal"),
           ("out_proj.bias", (hid,), "normal")]
    add("attn", at["window"] + at["full"], [
        ("Wqkv.weight", ((nq + 2 * nkv) * d, hid), "normal"),
        ("Wqkv.bias", ((nq + 2 * nkv) * d,), "normal")] + out + diff)
    add("cross", at["cross"], [
        ("Wqkv.weight", (nq * d, hid), "normal"),
        ("Wqkv.bias", (nq * d,), "normal")] + out + diff)
    # Mamba-1's own parameters as its authors initialise them: the depthwise
    # convolution as nn.Conv1d does (uniform within 1 / sqrt(width)), dt_proj
    # within dt_rank ** -0.5, A = 1..16 and dt = 1e-3..1e-1 spread over their
    # decades (a decay drawn as a normal forgets in two tokens and the gate
    # would not see a broken carry), D = 1
    cb, db = float(k) ** -0.5, float(dt_rank) ** -0.5
    add("mamba", at["mamba"], [
        ("in_proj.weight", (2 * d_inner, hid), "normal"),
        ("conv1d.weight", (d_inner, 1, k), ["uniform", -cb, cb]),
        ("conv1d.bias", (d_inner,), ["uniform", -cb, cb]),
        ("x_proj.weight", (dt_rank + 2 * n, d_inner), "normal"),
        ("dt_proj.weight", (d_inner, dt_rank), ["uniform", -db, db]),
        ("dt_proj.bias", (d_inner,), ["uniform", -6.9, -2.25]),
        ("A_log", (d_inner, n), ["uniform", 0.0, 2.77]),
        ("D", (d_inner,), "ones"),
        ("out_proj.weight", (hid, d_inner), "normal")])
    add("gmu", at["gmu"], [
        ("in_proj.weight", (d_inner, hid), "normal"),
        ("out_proj.weight", (hid, d_inner), "normal")])
    return table


def layer_norm(x, weight, bias, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32) + bias.astype(jnp.float32))


def mamba(cfg, w, j, a):
    """One Mamba-1 block over ``a`` (B, S, hidden), from a zero state:
    ``(y before the gate, z, the state after the last token (B, d_inner,
    d_state), the last d_conv - 1 inputs of the convolution (B, d_conv - 1,
    d_inner))``."""
    b, s, _ = a.shape
    d_inner, n, k, dt_rank = geometry(cfg)
    f32 = jnp.float32
    p = BLOCK["mamba"]
    uz = linear(a, w[p + "in_proj.weight"][j])
    u, z = uz[..., :d_inner], uz[..., d_inner:]
    conv_w = w[p + "conv1d.weight"][j].astype(f32)[:, 0, :]          # (C, K)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * conv_w[:, i] for i in range(k))
    up = jax.nn.silu(conv + w[p + "conv1d.bias"][j].astype(f32))
    rbc = linear(up, w[p + "x_proj.weight"][j])
    r, bm, cm = (rbc[..., :dt_rank], rbc[..., dt_rank:dt_rank + n],
                 rbc[..., dt_rank + n:])
    dt = jax.nn.softplus(linear(r, w[p + "dt_proj.weight"][j])
                         + w[p + "dt_proj.bias"][j].astype(f32))
    a_neg = -jnp.exp(w[p + "A_log"][j].astype(f32))                  # (C, N)
    d_skip = w[p + "D"][j].astype(f32)

    def step(state, t):                                   # state (B, C, N)
        up_t, dt_t, b_t, c_t = t
        state = (jnp.exp(dt_t[..., None] * a_neg) * state
                 + (dt_t * up_t)[..., None] * b_t[:, None, :])
        return state, jnp.einsum("bcn,bn->bc", state, c_t) + d_skip * up_t

    def time_first(t):
        return jnp.moveaxis(t, 1, 0)
    last, y = jax.lax.scan(step, jnp.zeros((b, d_inner, n), f32),
                           (time_first(up), time_first(dt), time_first(bm),
                            time_first(cm)))
    return jnp.moveaxis(y, 0, 1), z, last, padded[:, s:]


def softmax_attention(q, k, v, mask, scale):
    """``q`` (B, S, H, d), ``k``, ``v`` (B, S, H, d): one plain attention."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def pairs(x, control=None):
    """The two members of every pair of heads of ``x`` (B, S, H, d): head
    ``2j`` and ``2j + 1`` [assumed: neighbours, as the published
    ``view(.., H / 2, 2, d)`` has it]."""
    if control == "pair_by_halves":
        half = x.shape[2] // 2
        return x[:, :, :half], x[:, :, half:]
    return x[:, :, 0::2], x[:, :, 1::2]


def diff_attention(cfg, w, p, j, depth, q, k, v, mask, control=None):
    """Differential attention of the layer at ``depth`` whose tensors are
    row ``j`` of the stack under prefix ``p``: ``q`` (B, S, heads, d), ``k``,
    ``v`` (B, S, kv heads, d) -> (B, S, hidden) before ``W_o``."""
    b, s, nq, d = q.shape
    q1, q2 = pairs(q, control)
    k1, k2 = pairs(k, control)
    v1, v2 = pairs(v, control)
    rep = q1.shape[2] // k1.shape[2]

    def att(q_, k_, v_):
        return softmax_attention(q_, jnp.repeat(k_, rep, axis=2),
                                 jnp.repeat(v_, rep, axis=2), mask, d ** -0.5)
    a1 = jnp.concatenate([att(q1, k1, v1), att(q1, k1, v2)], axis=-1)
    a2 = jnp.concatenate([att(q2, k2, v1), att(q2, k2, v2)], axis=-1)
    f32 = jnp.float32

    def vec(name):
        return w[p + DIFF + "lambda_" + name][j].astype(f32)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(vec("q1") * vec("k1")))
           - jnp.exp(jnp.sum(vec("q2") * vec("k2"))) + lam_init)
    if control == "no_lambda":
        lam = 0.0
    if control == "lambda_init_only":
        lam = lam_init
    o = a1 - lam * a2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5) \
        * w[p + DIFF + "subln.weight"][j].astype(f32)
    return (o * (1.0 - lam_init)).reshape(b, s, nq * d)


def _walk(cfg, w, ids, control=None):
    """``(the final norm's output (B, S, hidden), states, tails)``: each
    Mamba-1 layer's state and convolution inputs after the last token."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    hid, eps = cfg["hidden_size"], cfg["layer_norm_eps"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, inter = hid // nq, cfg["intermediate_size"]
    kinds = layer_kinds(cfg)
    rows = {"attn": [i for i, x in enumerate(kinds)
                     if x in ("window", "full")]}
    rows.update({kind: [i for i, x in enumerate(kinds) if x == kind]
                 for kind in ("mamba", "cross", "gmu")})
    b, s = ids.shape
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    reach = cfg["sliding_window"] + (control == "window_plus_one")
    windowed = causal if control == "no_window" \
        else causal & (pos[:, None] - pos[None, :] < reach)
    embed = w["model.embed_tokens.weight"]
    x = embed[ids].astype(jnp.float32)
    states, tails = [], []
    memory = shared = window_kv = None
    for l, kind in enumerate(kinds):
        a = layer_norm(x, w[L + "input_layernorm.weight"][l],
                       w[L + "input_layernorm.bias"][l], eps)
        if kind == "mamba":
            p, j = BLOCK["mamba"], rows["mamba"].index(l)
            y, z, last, tail = mamba(cfg, w, j, a)
            states.append(last)
            tails.append(tail)
            gated = y * jax.nn.silu(z)
            if l == len(kinds) // 2:
                memory = gated if control == "m_after_gate" else y
            t_out = linear(gated, w[p + "out_proj.weight"][j])
        elif kind == "gmu":
            p, j = BLOCK["gmu"], rows["gmu"].index(l)
            t_out = linear(
                memory * jax.nn.silu(linear(a, w[p + "in_proj.weight"][j])),
                w[p + "out_proj.weight"][j])
        else:
            block = "cross" if kind == "cross" else "attn"
            p, j = BLOCK[block], rows[block].index(l)
            qkv = linear(a, w[p + "Wqkv.weight"][j]) \
                + w[p + "Wqkv.bias"][j].astype(jnp.float32)
            q = qkv[..., :nq * d].reshape(b, s, nq, d)
            if kind == "cross":
                k, v = shared
                mask = causal
                if control == "cross_reads_window_layer":
                    (k, v), mask = window_kv, windowed
            else:
                k = qkv[..., nq * d:(nq + nkv) * d].reshape(b, s, nkv, d)
                v = qkv[..., (nq + nkv) * d:].reshape(b, s, nkv, d)
                mask = causal if kind == "full" else windowed
                if kind == "full":
                    shared = (k, v)
                else:
                    window_kv = (k, v)
            o = diff_attention(cfg, w, p, j, l, q, k, v, mask, control)
            t_out = linear(o, w[p + "out_proj.weight"][j]) \
                + w[p + "out_proj.bias"][j].astype(jnp.float32)
        h = x + t_out
        gu = linear(layer_norm(h, w[L + "post_attention_layernorm.weight"][l],
                               w[L + "post_attention_layernorm.bias"][l],
                               eps), w[MLP + "gate_up_proj.weight"][l])
        x = h + linear(jax.nn.silu(gu[..., :inter]) * gu[..., inter:],
                       w[MLP + "down_proj.weight"][l])
    x = layer_norm(x, w["model.final_layernorm.weight"],
                   w["model.final_layernorm.bias"], eps)
    return x, states, tails


def final_hidden(cfg, w, ids, control=None):
    """What the tied head reads, ``(B, S, hidden)``: for a caller that takes
    the head a block of positions at a time (a long row's logits over
    200,064 words do not fit whole)."""
    return _walk(cfg, w, ids, control)[0]


def forward(cfg, w, ids, with_margins=False, control=None):
    """Float32 logits ``(B, S, vocab)``; nothing is routed, so the margins
    are ``inf`` everywhere."""
    logits = linear(final_hidden(cfg, w, ids, control),
                    w["model.embed_tokens.weight"])
    if with_margins:
        return logits, jnp.full(ids.shape, jnp.inf, jnp.float32)
    return logits


def final_states(cfg, w, ids):
    """The state every Mamba-1 layer holds after the last token of ``ids``,
    ``(mamba layers, B, d_inner, d_state)`` in float32: what a served
    sequence's state slot is held to (the logits of a short run cannot tell
    the precision the state is carried in; the state can)."""
    return jnp.stack(_walk(cfg, w, ids)[1])


def final_tails(cfg, w, ids):
    """The last ``d_conv - 1`` inputs of every Mamba-1 layer's convolution,
    ``(mamba layers, B, d_conv - 1, d_inner)``: a served slot's conv tail."""
    return jnp.stack(_walk(cfg, w, ids)[2])
