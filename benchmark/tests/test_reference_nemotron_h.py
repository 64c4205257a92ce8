"""``references/nemotron_h.py``'s own check. ``transformers`` 4.57.6 has no
``nemotron_h`` (so no ``reference_cases/nemotron_h.json``: ``test_reference``
would look up a class for the whole model). What it does ship holds the
reference piece by piece:

* the Mamba-2 mixer against Bamba's (``BambaMixer.torch_forward``) at
  ``mamba_n_groups`` 8, its gated norm (over the whole width) swapped for
  ``Zamba2RMSNormGated(width, group_size = width / 8)``: the in-projection's
  row order, the convolution, the recurrence, the groups' B / C and the norm
  by group; with Bamba's own norm it differs (the control
  ``norm_whole_width`` is that model);
* the router against ``DeepseekV3TopkRouter`` at ``n_group = topk_group = 1``
  (the same ``1e-20``);
* the whole against a second form written here, a loop over TOKENS that
  carries each layer's state, conv window and key / value cache by hand;
* the share, the margins, the controls and the weight table's round trip.
"""

import numpy as np
import pytest

from harness import build, weights

CFG = dict(
    model_type="nemotron_h", vocab_size=128, hidden_size=32,
    num_hidden_layers=7, hybrid_override_pattern="MEMEM*E",
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    mamba_num_heads=16, mamba_head_dim=4, n_groups=8, ssm_state_size=16,
    conv_kernel=4, chunk_size=128, expand=2, intermediate_size=24,
    layer_norm_epsilon=1e-5, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, n_group=1, topk_group=1, rope_theta=10000,
    time_step_min=0.001, time_step_max=0.1, use_conv_bias=True,
    tie_word_embeddings=False)
#: float32 sums in another order
ATOL = 2e-5


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("nemotron_h")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=2**31 + 64)


def test_the_mixer_is_bambas_at_8_groups_with_zamba2s_norm_by_group(ref, w):
    import jax
    import jax.numpy as jnp
    import torch
    from transformers import BambaConfig
    from transformers.models.bamba.modeling_bamba import BambaMixer
    from transformers.models.zamba2.modeling_zamba2 import Zamba2RMSNormGated
    width = CFG["mamba_num_heads"] * CFG["mamba_head_dim"]
    assert width == CFG["expand"] * CFG["hidden_size"]    # Bamba's own rule
    mixer = BambaMixer(BambaConfig(
        hidden_size=32, mamba_n_heads=16, mamba_d_head=4, mamba_n_groups=8,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
        mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
        rms_norm_eps=1e-5, hidden_act="silu"), layer_idx=0).eval()
    whole_width = mixer.norm
    by_group = Zamba2RMSNormGated(width, group_size=width // 8, eps=1e-5)
    view = weights.HfView(ref.weight_shapes(CFG), w,
                          dtype=np.dtype("float32"))
    layer = 2                                   # the second M layer

    def tensor(name):
        return torch.tensor(np.ascontiguousarray(
            view[f"backbone.layers.{layer}.mixer.{name}"]))
    with torch.no_grad():
        for name in ("in_proj.weight", "conv1d.weight", "conv1d.bias",
                     "out_proj.weight"):
            mod, leaf = name.split(".")
            getattr(getattr(mixer, mod), leaf).copy_(tensor(name))
        for name in ("dt_bias", "A_log", "D"):
            getattr(mixer, name).copy_(tensor(name))
        by_group.weight.copy_(tensor("norm.weight"))
        whole_width.weight.copy_(tensor("norm.weight"))
    u = np.random.default_rng(4).normal(size=(2, 21, 32)).astype(np.float32)
    with torch.no_grad():
        mixer.norm = by_group
        want = mixer.torch_forward(torch.tensor(u)).numpy()
        mixer.norm = whole_width
        bambas = mixer.torch_forward(torch.tensor(u)).numpy()
    with jax.default_matmul_precision("highest"):
        got, state = ref._mamba(CFG, w, 1, jnp.asarray(u), None)
        whole, _ = ref._mamba(CFG, w, 1, jnp.asarray(u), "norm_whole_width")
        group0, _ = ref._mamba(CFG, w, 1, jnp.asarray(u), "bc_group0")
    assert float(np.abs(want).max()) > 0.05
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert state.shape == (2, 16, 4, 16)
    # Bamba's own norm is the control, not the model
    np.testing.assert_allclose(whole, bambas, atol=ATOL)
    assert float(np.abs(want - bambas).max()) > 100 * ATOL
    assert float(np.abs(group0 - want).max()) > 100 * ATOL


def test_the_routing_is_deepseek_v3s(ref):
    import jax.numpy as jnp
    import torch
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import \
        DeepseekV3TopkRouter
    router = DeepseekV3TopkRouter(DeepseekV3Config(
        hidden_size=32, n_routed_experts=8, num_experts_per_tok=2, n_group=1,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5))
    rng = np.random.default_rng(62)
    gate = rng.normal(size=(8, 32)).astype(np.float32) * 0.3
    bias = rng.uniform(-0.2, 0.2, size=(8,)).astype(np.float32)
    x = rng.normal(size=(40, 32)).astype(np.float32)
    with torch.no_grad():
        router.weight.copy_(torch.tensor(gate))
        router.e_score_correction_bias.copy_(torch.tensor(bias))
        want_e, want_w = router(torch.tensor(x))
    top, picked, margin = ref.route(CFG, jnp.asarray(x @ gate.T),
                                    jnp.asarray(bias))
    order = np.argsort(np.asarray(picked), -1)
    want_order = np.argsort(want_e.numpy(), -1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(picked), order, -1),
        np.take_along_axis(want_e.numpy(), want_order, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(top), order, -1),
        np.take_along_axis(want_w.numpy(), want_order, -1), rtol=2e-6)
    # the bias picked somewhere it did not weigh
    plain = np.argsort(-(x @ gate.T), axis=-1)[:, :2]
    assert (np.sort(plain, -1) != np.sort(np.asarray(picked), -1)).any()
    assert margin.shape == (40,) and float(margin.min()) >= 0
    for control in ("bias_dropped", "renorm_dropped", "scaling_dropped",
                    "softmax_router"):
        other, _, _ = ref.route(CFG, jnp.asarray(x @ gate.T),
                                jnp.asarray(bias), control)
        assert float(np.abs(np.sort(np.asarray(other), -1)
                            - np.sort(np.asarray(top), -1)).max()) > 1e-3


def _token_loop(ref, cfg, w, ids):
    """The whole model a TOKEN at a time, in numpy: every layer carries its
    state (M), its conv window (M) or its keys and values (*) by hand."""
    f = {k: np.asarray(v, np.float32) for k, v in w.items()}
    pattern, eps = cfg["hybrid_override_pattern"], cfg["layer_norm_epsilon"]
    nh, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_inner, gn = nh * hd, g * n
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])

    def norm(x, weight):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * weight

    def silu(x):
        return x / (1 + np.exp(-x))
    carried = [dict() for _ in pattern]
    logits = []
    for t in ids:
        x = f["backbone.embeddings.weight"][t]
        for i, kind in enumerate(pattern):
            j, c = pattern[:i].count(kind), carried[i]
            h = norm(x, f[ref.L + "norm.weight"][i])
            if kind == "M":
                zxbcdt = f[ref.MIX + "in_proj.weight"][j] @ h
                z, xbc, dt = np.split(zxbcdt, [d_inner,
                                               2 * d_inner + 2 * gn])
                window = np.concatenate([c.get("window",
                                               np.zeros((k - 1, xbc.size))),
                                         xbc[None]])
                c["window"] = window[1:]
                taps = f[ref.MIX + "conv1d.weight"][j][:, 0, :]
                xbc = silu((window.T * taps).sum(-1)
                           + f[ref.MIX + "conv1d.bias"][j])
                xs = xbc[:d_inner].reshape(nh, hd)
                bm = xbc[d_inner:d_inner + gn].reshape(g, n)
                cm = xbc[d_inner + gn:].reshape(g, n)
                dt = np.log1p(np.exp(dt + f[ref.MIX + "dt_bias"][j]))
                a = -np.exp(f[ref.MIX + "A_log"][j])
                state = c.get("state", np.zeros((nh, hd, n)))
                y = np.zeros((nh, hd))
                for head in range(nh):
                    grp = head // (nh // g)
                    state[head] = (np.exp(dt[head] * a[head]) * state[head]
                                   + dt[head] * np.outer(xs[head], bm[grp]))
                    y[head] = state[head] @ cm[grp] \
                        + f[ref.MIX + "D"][j][head] * xs[head]
                c["state"] = state
                y = (y.reshape(-1) * silu(z)).reshape(g, d_inner // g)
                y = (y / np.sqrt(np.mean(y * y, -1, keepdims=True) + eps)
                     ).reshape(-1) * f[ref.MIX + "norm.weight"][j]
                out = f[ref.MIX + "out_proj.weight"][j] @ y
            elif kind == "*":
                q = (f[ref.MIX + "q_proj.weight"][j] @ h).reshape(nq, d)
                c.setdefault("k", []).append(
                    (f[ref.MIX + "k_proj.weight"][j] @ h).reshape(nkv, d))
                c.setdefault("v", []).append(
                    (f[ref.MIX + "v_proj.weight"][j] @ h).reshape(nkv, d))
                keys, values = np.stack(c["k"]), np.stack(c["v"])
                heads = []
                for head in range(nq):
                    kv = head // (nq // nkv)
                    s = keys[:, kv] @ q[head] / np.sqrt(d)
                    p = np.exp(s - s.max())
                    heads.append((p / p.sum()) @ values[:, kv])
                out = f[ref.MIX + "o_proj.weight"][j] @ np.concatenate(heads)
            else:
                s = 1 / (1 + np.exp(-(f[ref.MIX + "gate.weight"][j] @ h)))
                biased = s + f[ref.MIX + "gate.e_score_correction_bias"][j]
                picked = np.argsort(-biased, kind="stable")[
                    :cfg["num_experts_per_tok"]]
                weight = s[picked] / (s[picked].sum() + 1e-20) \
                    * cfg["routed_scaling_factor"]
                first = cfg.get("first_expert", 0)
                out = np.zeros_like(h)
                for e, we in zip(picked, weight):
                    if first <= e < first + cfg["n_routed_experts"]:
                        up = f[ref.EXPERT + "up_proj.weight"][j][e - first]
                        down = f[ref.EXPERT + "down_proj.weight"][j][
                            e - first]
                        out += we * (down @ np.maximum(up @ h, 0) ** 2)
                out += f[ref.SHARED + "down_proj.weight"][j] @ np.maximum(
                    f[ref.SHARED + "up_proj.weight"][j] @ h, 0) ** 2
            x = x + out
        logits.append(f["lm_head.weight"] @ norm(
            x, f["backbone.norm_f.weight"]))
    states = np.stack([c["state"] for c, kind in zip(carried, pattern)
                       if kind == "M"])
    return np.stack(logits), states


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_the_forward_is_the_loop_over_tokens(ref, w, share):
    import jax
    import jax.numpy as jnp
    cfg = dict(CFG, n_routed_experts=4, router_n_routed_experts=8,
               first_expert=2) if share else CFG
    if share:
        w = dict(w)
        for name in ("up_proj.weight", "down_proj.weight"):
            w[ref.EXPERT + name] = w[ref.EXPERT + name][:, 2:6]
    ids = np.random.default_rng(3).integers(1, 128, (1, 30))
    want, want_states = _token_loop(ref, cfg, w, ids[0])
    with jax.default_matmul_precision("highest"):
        got = ref.forward(cfg, w, jnp.asarray(ids))
        states = ref.final_states(cfg, w, jnp.asarray(ids))
    assert float(np.abs(want).max()) > 0.3
    np.testing.assert_allclose(got[0], want, atol=ATOL)
    assert states.shape == (3, 1, 16, 4, 16)
    np.testing.assert_allclose(states[:, 0], want_states, atol=ATOL)


def test_the_margins_the_controls_and_causality(ref, w):
    import jax
    import jax.numpy as jnp
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 128, (2, 40)))
    with jax.default_matmul_precision("highest"):
        logits, margins = ref.forward(CFG, w, ids, with_margins=True)
        again = ref.forward(CFG, w, ids.at[:, 30].set(5))
        faulty = {c: ref.forward(CFG, w, ids, control=c)
                  for c in ref.CONTROLS}
        blocked, ref.ATTEND_BLOCK = ref.ATTEND_BLOCK, 16
        try:
            by_blocks = ref.forward(CFG, w, ids)
        finally:
            ref.ATTEND_BLOCK = blocked
    assert logits.shape == (2, 40, 128) and logits.dtype == jnp.float32
    assert margins.shape == (2, 40)
    assert np.isfinite(np.asarray(margins)).all() and float(
        margins.min()) >= 0
    np.testing.assert_array_equal(logits[:, :30], again[:, :30])
    assert float(jnp.abs(logits[:, 30:] - again[:, 30:]).max()) > 1e-3
    np.testing.assert_allclose(by_blocks, logits, atol=ATOL)
    assert len(ref.CONTROLS) == 11
    for control, got in faulty.items():
        # (a toy's attention is near uniform: a rotation of q and k of 8
        # lanes at a score spread of 0.01 moves a logit by 1e-4)
        floor = 5e-5 if control == "rotary_applied" else 5e-4
        assert float(jnp.abs(got - logits).max()) > floor, control
    with pytest.raises(ValueError):
        ref.forward(CFG, w, ids, control="no_such_fault")
    with pytest.raises(ValueError):
        ref.forward(dict(CFG, tie_word_embeddings=True), w, ids)


def test_hfview_round_trips_the_table(ref, w):
    table = ref.weight_shapes(CFG)
    assert {k: v.shape for k, v in w.items()} == \
        {k: tuple(e["shape"]) for k, e in table.items()}
    view = weights.HfView(table, w)
    mixers, attention, experts = [0, 2, 4], [5], [1, 3, 6]
    for name, entry in table.items():
        if "{i}" not in name:
            np.testing.assert_array_equal(np.asarray(w[name]), view[name])
            continue
        layers = weights.layers_of(name, entry)
        assert layers == (
            list(range(7)) if name.endswith("}.norm.weight")
            else attention if "_proj.weight" in name and any(
                p in name for p in ("q_proj", "k_proj", "v_proj", "o_proj"))
            else experts if "gate." in name or "experts" in name
            else mixers), name
        for row, i in enumerate(layers):
            if "{e}" in name:
                for e in (0, 7):
                    np.testing.assert_array_equal(
                        np.asarray(w[name][row, e]),
                        view[name.format(i=i, e=e)])
            else:
                np.testing.assert_array_equal(np.asarray(w[name][row]),
                                              view[name.format(i=i)])
    assert "backbone.layers.1.mixer.in_proj.weight" not in view
    assert "backbone.layers.0.mixer.gate.weight" not in view
    assert "backbone.layers.5.mixer.experts.0.up_proj.weight" not in view
    assert view["lm_head.weight"].shape == (128, 32)          # untied
    assert view["backbone.layers.6.mixer.experts.7.down_proj.weight"].shape \
        == (32, 24)
    assert view["backbone.layers.3.mixer.shared_experts.up_proj.weight"
                ].shape == (48, 32)
    assert view["backbone.layers.4.mixer.conv1d.weight"].shape == (
        64 + 2 * 8 * 16, 1, 4)
    assert view["backbone.layers.4.mixer.in_proj.weight"].shape == (
        2 * 64 + 2 * 8 * 16 + 16, 32)
    # the selection bias non-zero, the steps log-uniform over the published
    # range, A over 1 .. 16
    bias = np.asarray(
        w["backbone.layers.{i}.mixer.gate.e_score_correction_bias"],
        np.float32)
    assert np.abs(bias).max() <= 0.2 and np.abs(bias).mean() > 0.04
    dt = np.log1p(np.exp(np.asarray(
        w["backbone.layers.{i}.mixer.dt_bias"], np.float32)))
    assert 0.9e-3 < dt.min() < 5e-3 and 0.03 < dt.max() < 0.11
    a = np.exp(np.asarray(w["backbone.layers.{i}.mixer.A_log"], np.float32))
    assert 1.0 <= a.min() < 2.0 and 8.0 < a.max() <= 16.1
