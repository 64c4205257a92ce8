"""``references/phi4flash.py``'s own check. ``transformers`` 4.57.6 has no
``phi4flash`` class (so no ``reference_cases/phi4flash.json``:
``test_reference`` would look up ``Phi4flashForCausalLM``). The reference is
held to SECOND writings, piece by piece and whole:

* its Mamba-1 block to ``transformers.models.mamba``'s ``MambaMixer`` slow
  path (the same selective scan, written by others) at a toy size in float32;
* its differential attention (four plain softmax attentions) to the
  placed-query identity: each query in its own half of a pair-wide row, ONE
  grouped-query attention over kv pairs, then the combine;
* the whole to a token-by-token recurrent walk in NumPy float64 that carries
  a Mamba state, a conv window and a list of keys and values, one token at a
  time, and whose cross layers index layer ``N / 2 + 1``'s list;

every control moves the logits, the layer rules are checked by hand, and the
weight table is round-tripped through ``HfView`` (the keys that share a
published name across layer kinds format to that ONE name)."""

import math

import numpy as np
import pytest

from harness import build, weights

CFG = dict(
    model_type="phi4flash", vocab_size=96, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=8,
    intermediate_size=48, sliding_window=5, layer_norm_eps=1e-5,
    mb_per_layer=2, tie_word_embeddings=True, hidden_act="silu",
    mamba_d_state=4, mamba_dt_rank=3, max_position_embeddings=256)
#: float32 against float64: sums in another order
ATOL = 2e-5
SEED = 2 ** 31 + 54


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("phi4flash")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=SEED)


def test_the_layer_rules_by_hand(ref):
    kinds = ref.layer_kinds(dict(CFG, num_hidden_layers=32))
    assert [i for i, k in enumerate(kinds) if k == "mamba"] == \
        list(range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == "window"] == \
        list(range(1, 16, 2))
    assert kinds.index("full") == 17 and kinds.count("full") == 1
    assert [i for i, k in enumerate(kinds) if k == "cross"] == \
        list(range(19, 32, 2))
    assert [i for i, k in enumerate(kinds) if k == "gmu"] == \
        list(range(18, 32, 2))
    assert ref.layer_kinds(CFG) == ["mamba", "window", "mamba", "window",
                                    "mamba", "full", "gmu", "cross"]
    with pytest.raises(ValueError):
        ref.layer_kinds(dict(CFG, num_hidden_layers=6))
    assert ref.geometry(dict(hidden_size=2560)) == (5120, 16, 4, 160)


def test_the_table_round_trips_through_the_published_names(ref, w):
    table = ref.weight_shapes(CFG)
    view = weights.HfView(table, w, dtype=np.dtype("float32"))
    # a layer holds its temporal block as ``attn`` whatever its kind: the
    # same published name, another shape by layer kind
    assert view["model.layers.0.attn.in_proj.weight"].shape == (128, 32)
    assert view["model.layers.6.attn.in_proj.weight"].shape == (64, 32)
    assert view["model.layers.1.attn.Wqkv.weight"].shape == (64, 32)
    assert view["model.layers.7.attn.Wqkv.weight"].shape == (32, 32)
    assert view["model.layers.4.attn.out_proj.weight"].shape == (32, 64)
    assert view["model.layers.5.attn.out_proj.weight"].shape == (32, 32)
    assert view["model.layers.5.attn.inner_cross_attn.lambda_q1"].shape \
        == (8,)
    assert "model.layers.0.attn.Wqkv.weight" not in view
    assert "model.layers.1.attn.in_proj.weight" not in view
    assert not [k for k in view if "{" in k or "None" in k]
    n = sum(math.prod(e["shape"]) for e in table.values())
    assert sum(v.size for v in (view[k] for k in view)) == n


def test_the_mamba_block_is_transformers_slow_path(ref, w):
    """``mamba`` against ``MambaMixer.slow_forward`` (transformers' own
    writing of Mamba-1's selective scan) on the same weights, float32."""
    torch = pytest.importorskip("torch")
    from transformers.models.mamba.configuration_mamba import MambaConfig
    from transformers.models.mamba.modeling_mamba import MambaMixer
    d_inner, n, k, dt_rank = ref.geometry(CFG)
    mixer = MambaMixer(MambaConfig(
        hidden_size=CFG["hidden_size"], state_size=n, conv_kernel=k,
        expand=2, time_step_rank=dt_rank, use_conv_bias=True, use_bias=False,
        hidden_act="silu", num_hidden_layers=1, vocab_size=8), layer_idx=0)
    p, j = ref.BLOCK["mamba"], 1
    names = {"in_proj.weight": "in_proj.weight",
             "conv1d.weight": "conv1d.weight", "conv1d.bias": "conv1d.bias",
             "x_proj.weight": "x_proj.weight",
             "dt_proj.weight": "dt_proj.weight",
             "dt_proj.bias": "dt_proj.bias", "A_log": "A_log", "D": "D",
             "out_proj.weight": "out_proj.weight"}
    state = {theirs: torch.tensor(np.asarray(w[p + ours][j], np.float32))
             for ours, theirs in names.items()}
    mixer.load_state_dict(state)
    rng = np.random.default_rng(1)
    a = rng.normal(size=(2, 11, CFG["hidden_size"])).astype(np.float32)
    with torch.no_grad():
        want = mixer.slow_forward(torch.tensor(a)).numpy()
    import jax.numpy as jnp
    from harness.reference import linear
    y, z, last, tail = ref.mamba(CFG, w, j, jnp.asarray(a))
    import jax
    got = linear(y * jax.nn.silu(z), w[p + "out_proj.weight"][j])
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=1e-4)
    assert last.shape == (2, d_inner, n) and tail.shape == (2, k - 1, d_inner)


def test_four_attentions_are_one_over_placed_queries(ref, w):
    """The identity the program serves by: with a pair's keys side by side
    in one row ``[k1 | k2]`` and its values ``[v1 | v2]``, query ``q[2j +
    c]`` placed in half ``c`` of a row (zeros in the other) scores ``q .
    k_{c+1}`` and attends the pair-wide value: ONE grouped-query softmax
    attention gives ``A1`` and ``A2`` of every pair."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    b, s, nq, nkv, d = 2, 9, 4, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, n, d)), jnp.float32)
               for n in (nq, nkv, nkv))
    pos = jnp.arange(s)
    mask = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < 5)
    p, j, depth = ref.BLOCK["attn"], 2, 5
    want = ref.diff_attention(CFG, w, p, j, depth, q, k, v, mask)
    zero = jnp.zeros((b, s, nq // 2, d), jnp.float32)
    placed = jnp.stack([jnp.concatenate([q[:, :, 0::2], zero], -1),
                        jnp.concatenate([zero, q[:, :, 1::2]], -1)],
                       axis=3).reshape(b, s, nq, 2 * d)
    kp, vp = (x.reshape(b, s, nkv // 2, 2 * d) for x in (k, v))
    rep = nq // (nkv // 2)
    out = ref.softmax_attention(placed, jnp.repeat(kp, rep, axis=2),
                                jnp.repeat(vp, rep, axis=2), mask, d ** -0.5)
    a1, a2 = out[:, :, 0::2], out[:, :, 1::2]

    def vec(name):
        return w[p + ref.DIFF + "lambda_" + name][j].astype(jnp.float32)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(vec("q1") * vec("k1")))
           - jnp.exp(jnp.sum(vec("q2") * vec("k2"))) + lam_init)
    # the seeded vectors keep lam away from 0, from lam_init and from 1
    assert 0.05 < float(lam) < lam_init - 0.02
    o = a1 - lam * a2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) \
        * w[p + ref.DIFF + "subln.weight"][j].astype(jnp.float32)
    got = (o * (1 - lam_init)).reshape(b, s, nq * d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def _ln(x, g, b, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / np.sqrt(v + eps) * g + b


def _silu(x):
    return x / (1 + np.exp(-x))


def token_by_token(ref, cfg, w, ids):
    """Logits (S, vocab) of ONE sequence, float64: a decoder that sees one
    token at a time and keeps what a server keeps - each Mamba layer's state
    and the last ``d_conv - 1`` inputs of its convolution, each attention
    layer's keys and values (a window layer's: the last ``sliding_window``),
    and NOTHING for a cross layer or a Gated Memory Unit, which read the full
    layer's list and the last mixer's output of the same token."""
    f = {k: np.asarray(v, np.float64) for k, v in w.items()}
    hid, nq, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    d, inter = hid // nq, cfg["intermediate_size"]
    d_inner, n, kw, dt_rank = ref.geometry(cfg)
    kinds = ref.layer_kinds(cfg)
    rows = {b: [i for i, x in enumerate(kinds) if x in ks]
            for b, ks in (("attn", ("window", "full")), ("mamba", ("mamba",)),
                          ("cross", ("cross",)), ("gmu", ("gmu",)))}
    L = "model.layers.{i}."
    state = {l: np.zeros((d_inner, n)) for l in rows["mamba"]}
    conv = {l: np.zeros((kw - 1, d_inner)) for l in rows["mamba"]}
    cache = {l: ([], []) for l in rows["attn"]}
    logits = []

    def attend(q, keys, values, p, j, depth):
        heads = []
        for h in range(nq // 2):
            g = h // ((nq // 2) // (nkv // 2))
            value = np.stack([np.concatenate([vv[2 * g], vv[2 * g + 1]])
                              for vv in values])
            both = []
            for c in range(2):
                sc = np.array([kk[2 * g + c] @ q[2 * h + c]
                               for kk in keys]) / math.sqrt(d)
                pr = np.exp(sc - sc.max())
                both.append((pr / pr.sum()) @ value)
            lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
            lv = {x: f[p + ref.DIFF + "lambda_" + x][j]
                  for x in ("q1", "k1", "q2", "k2")}
            lam = (math.exp(lv["q1"] @ lv["k1"])
                   - math.exp(lv["q2"] @ lv["k2"]) + lam_init)
            o = both[0] - lam * both[1]
            o = o / np.sqrt(np.mean(o * o) + 1e-5) \
                * f[p + ref.DIFF + "subln.weight"][j]
            heads.append(o * (1 - lam_init))
        return np.concatenate(heads)

    for tok in ids:
        x = f["model.embed_tokens.weight"][tok]
        memory = None
        for l, kind in enumerate(kinds):
            a = _ln(x, f[L + "input_layernorm.weight"][l],
                    f[L + "input_layernorm.bias"][l])
            if kind == "mamba":
                p, j = ref.BLOCK["mamba"], rows["mamba"].index(l)
                uz = f[p + "in_proj.weight"][j] @ a
                u, z = uz[:d_inner], uz[d_inner:]
                window = np.concatenate([conv[l], u[None]])
                conv[l] = window[1:]
                up = _silu((window * f[p + "conv1d.weight"][j][:, 0].T
                            ).sum(0) + f[p + "conv1d.bias"][j])
                rbc = f[p + "x_proj.weight"][j] @ up
                r, bm, cm = (rbc[:dt_rank], rbc[dt_rank:dt_rank + n],
                             rbc[dt_rank + n:])
                dt = np.log1p(np.exp(f[p + "dt_proj.weight"][j] @ r
                                     + f[p + "dt_proj.bias"][j]))
                a_neg = -np.exp(f[p + "A_log"][j])
                state[l] = (np.exp(dt[:, None] * a_neg) * state[l]
                            + (dt * up)[:, None] * bm[None, :])
                y = state[l] @ cm + f[p + "D"][j] * up
                memory = y
                t_out = f[p + "out_proj.weight"][j] @ (y * _silu(z))
            elif kind == "gmu":
                p, j = ref.BLOCK["gmu"], rows["gmu"].index(l)
                t_out = f[p + "out_proj.weight"][j] @ (
                    memory * _silu(f[p + "in_proj.weight"][j] @ a))
            else:
                block = "cross" if kind == "cross" else "attn"
                p, j = ref.BLOCK[block], rows[block].index(l)
                qkv = f[p + "Wqkv.weight"][j] @ a + f[p + "Wqkv.bias"][j]
                q = qkv[:nq * d].reshape(nq, d)
                if kind == "cross":
                    keys, values = cache[kinds.index("full")]
                else:
                    keys, values = cache[l]
                    keys.append(qkv[nq * d:(nq + nkv) * d].reshape(nkv, d))
                    values.append(qkv[(nq + nkv) * d:].reshape(nkv, d))
                    if kind == "window":
                        del keys[:-cfg["sliding_window"]]
                        del values[:-cfg["sliding_window"]]
                t_out = f[p + "out_proj.weight"][j] @ attend(
                    q, keys, values, p, j, l) + f[p + "out_proj.bias"][j]
            h = x + t_out
            gu = f[L + "mlp.gate_up_proj.weight"][l] @ _ln(
                h, f[L + "post_attention_layernorm.weight"][l],
                f[L + "post_attention_layernorm.bias"][l])
            x = h + f[L + "mlp.down_proj.weight"][l] @ (
                _silu(gu[:inter]) * gu[inter:])
        x = _ln(x, f["model.final_layernorm.weight"],
                f["model.final_layernorm.bias"])
        logits.append(f["model.embed_tokens.weight"] @ x)
    return np.stack(logits), state, conv


def test_forward_is_the_token_by_token_walk(ref, w):
    import jax.numpy as jnp
    ids = np.random.default_rng(3).integers(1, CFG["vocab_size"], size=19)
    want, state, conv = token_by_token(ref, CFG, w, ids)
    got = np.asarray(ref.forward(CFG, w, jnp.asarray(ids[None])))[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-4)
    # what a served slot is held to: the state and the convolution's inputs
    # after the last token, by Mamba layer
    states = np.asarray(ref.final_states(CFG, w, jnp.asarray(ids[None])))
    tails = np.asarray(ref.final_tails(CFG, w, jnp.asarray(ids[None])))
    for j, l in enumerate(sorted(state)):
        np.testing.assert_allclose(states[j, 0], state[l], atol=ATOL)
        np.testing.assert_allclose(tails[j, 0], conv[l], atol=ATOL)
    logits, margins = ref.forward(CFG, w, jnp.asarray(ids[None]),
                                  with_margins=True)
    assert np.isinf(np.asarray(margins)).all()


def test_every_control_moves_the_logits(ref, w):
    import jax.numpy as jnp
    ids = jnp.asarray(np.random.default_rng(4).integers(
        1, CFG["vocab_size"], size=(2, 17)))
    sound = np.asarray(ref.forward(CFG, w, ids))
    for control in ref.CONTROLS:
        moved = np.asarray(ref.forward(CFG, w, ids, control=control))
        assert np.abs(moved - sound).max() > 10 * ATOL, control
    with pytest.raises(ValueError, match="unknown control"):
        ref.forward(CFG, w, ids, control="nothing")
