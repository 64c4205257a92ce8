"""The references against the HF implementations in float32 at a toy size,
and the seeded gate weights that feed them. ``harness/reference.py``'s two
types are the cases below; a reference found by name
(``references/<model_type>.py``) brings its case as a file
``tests/reference_cases/<model_type>.json``: ``config`` (published keys at a
toy size) and ``transformers`` (the stem of its ``<X>Config`` /
``<X>ForCausalLM``)."""

import glob
import json
import os

import numpy as np
import pytest

from harness import build, weights

TOY_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")

TOY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           num_hidden_layers=2, vocab_size=128, rms_norm_eps=1e-5,
           max_position_embeddings=128, tie_word_embeddings=False,
           hidden_act="silu", attention_bias=False)
CASES = {
    "olmoe": dict(TOY, model_type="olmoe", num_key_value_heads=4,
                  intermediate_size=32, num_experts=8, num_experts_per_tok=2,
                  norm_topk_prob=False, rope_theta=10000.0, clip_qkv=None),
    "mistral": dict(TOY, model_type="mistral", intermediate_size=96,
                    head_dim=32, rope_theta=1e6, sliding_window=None),
}
HF_STEM = {"olmoe": "Olmoe", "mistral": "Mistral"}
for _path in sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "reference_cases",
        "*.json"))):
    with open(_path) as _f:
        _case = json.load(_f)
    _name = os.path.splitext(os.path.basename(_path))[0]
    CASES[_name], HF_STEM[_name] = _case["config"], _case["transformers"]


@pytest.fixture(autouse=True)
def toy_files(monkeypatch):
    """``references/granite.py`` lives with the toy benchmark."""
    monkeypatch.setattr(build, "DATA_ROOT", TOY_ROOT)


def _hf_model(cfg):
    import torch
    import transformers
    torch.manual_seed(0)
    kw = {k: v for k, v in cfg.items() if k != "model_type"}
    name = HF_STEM[cfg["model_type"]]
    model = getattr(transformers, name + "ForCausalLM")(
        getattr(transformers, name + "Config")(**kw))
    with torch.no_grad():           # norm weights away from 1, as in the gate
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.1 * torch.randn_like(p))
    return model.float().eval()


def _stacked(table, sd):
    """A flat published state dict -> the reference's stacked names."""
    out = {}
    for name, entry in table.items():
        def get(**at):
            return sd[name.format(**at)].numpy()
        if "{i}" not in name:
            arr = get()
        else:
            layers = weights.layers_of(name, entry)
            if "{e}" in name:
                arr = np.stack([np.stack([get(i=i, e=e) for e in
                                          range(entry["shape"][1])])
                                for i in layers])
            else:
                arr = np.stack([get(i=i) for i in layers])
        assert arr.shape == tuple(entry["shape"]), (name, arr.shape)
        out[name] = arr
    return out


@pytest.mark.parametrize("family", sorted(CASES))
def test_reference_matches_hf(family):
    import jax
    import torch
    cfg = CASES[family]
    model = _hf_model(cfg)
    ref = build.load_reference(family)
    w = _stacked(ref.weight_shapes(cfg), model.state_dict())
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 24))
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got, margins = ref.forward(cfg, w, ids, with_margins=True)
    got = np.asarray(got)
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
    assert margins.shape == ids.shape
    assert np.isinf(np.asarray(margins)).all() == ("num_experts" not in cfg)


@pytest.mark.parametrize("family", sorted(CASES))
def test_hfview_round_trips_the_generated_weights(family):
    cfg = CASES[family]
    table = build.load_reference(family).weight_shapes(cfg)
    w = weights.make_weights(table, seed=2**31 + 7)
    assert {k: v.shape for k, v in w.items()} == \
        {k: tuple(e["shape"]) for k, e in table.items()}
    view = weights.HfView(table, w)
    again = _stacked(table, {k: _Torchish(v) for k, v in view.items()})
    for name in w:
        np.testing.assert_array_equal(np.asarray(w[name]), again[name])
    norm = np.asarray(w["model.norm.weight"]).astype(np.float32)
    assert abs(norm.mean() - 1.0) < 0.1        # norms sit around one
    emb = np.asarray(w["model.embed_tokens.weight"]).astype(np.float32)
    assert abs(emb.std() - weights.INIT_STD) < 0.005


class _Torchish:
    def __init__(self, a):
        self._a = a

    def numpy(self):
        return self._a


# ---------------------------------------------------------------------------
# the table: layers, initialisers, and the numbers of the two built-in types
# ---------------------------------------------------------------------------

#: a made-up architecture of 5 layers: a mixer on layers 0, 1, 3, 4 and
#: attention on layer 2 alone, one tensor per initialiser
MADE_UP = {
    "model.embed_tokens.weight": {"shape": (32, 8), "init": "normal"},
    "model.layers.{i}.input_layernorm.weight": {"shape": (5, 8),
                                                "init": "norm"},
    "model.layers.{i}.mamba.in_proj.weight": {
        "shape": (4, 24, 8), "layers": [0, 1, 3, 4], "init": "normal"},
    "model.layers.{i}.mamba.D": {"shape": (4, 6), "layers": [0, 1, 3, 4],
                                 "init": "ones"},
    "model.layers.{i}.mamba.A_log": {
        "shape": (4, 6), "layers": [0, 1, 3, 4],
        "init": ["log_uniform", 1.0, 16.0]},
    "model.layers.{i}.mamba.dt_bias": {
        "shape": (4, 6), "layers": [0, 1, 3, 4],
        "init": ["uniform", -4.0, -1.0]},
    "model.layers.{i}.self_attn.q_proj.weight": {
        "shape": (1, 8, 8), "layers": [2], "init": "normal"},
    "model.layers.{i}.moe.experts.{e}.w.weight": {
        "shape": (1, 3, 4, 8), "layers": [2], "init": "normal"},
}


def test_a_table_places_tensors_on_their_layers_and_draws_as_it_says():
    w = weights.make_weights(MADE_UP, seed=2**31 + 9)
    assert {k: v.shape for k, v in w.items()} == \
        {k: e["shape"] for k, e in MADE_UP.items()}
    assert all(str(v.dtype) == "bfloat16" for v in w.values())
    view = weights.HfView(MADE_UP, w)
    # flat published names: present on the layers named, absent elsewhere
    assert "model.layers.3.mamba.A_log" in view
    assert "model.layers.2.mamba.A_log" not in view
    assert "model.layers.2.self_attn.q_proj.weight" in view
    assert "model.layers.0.self_attn.q_proj.weight" not in view
    assert sorted(k for k in view if ".experts." in k) == [
        f"model.layers.2.moe.experts.{e}.w.weight" for e in range(3)]
    assert len(view) == 1 + 5 + 4 * 4 + 1 + 3
    # ... and they round-trip: row j of a stacked tensor is layer layers[j]
    again = _stacked(MADE_UP, {k: _Torchish(v) for k, v in view.items()})
    for name in w:
        np.testing.assert_array_equal(np.asarray(w[name]), again[name])
    np.testing.assert_array_equal(
        view["model.layers.3.mamba.dt_bias"],
        np.asarray(w["model.layers.{i}.mamba.dt_bias"])[2])
    # the ranges hold (bfloat16 rounds to the nearest, so the ends included)
    f32 = {k.rsplit(".", 1)[-1]: np.asarray(v).astype(np.float32)
           for k, v in w.items() if ".mamba." in k}
    assert (f32["D"] == 1.0).all()
    assert 1.0 <= f32["A_log"].min() < 2.0 and 8.0 < f32["A_log"].max() <= 16.0
    assert -4.0 <= f32["dt_bias"].min() and f32["dt_bias"].max() <= -1.0
    assert len(np.unique(f32["dt_bias"])) > 12
    # half of a log-uniform draw lies under the geometric mean of its ends
    assert 0.2 < (f32["A_log"] < 4.0).mean() < 0.8


@pytest.mark.parametrize("bad", [
    {"shape": (2, 4), "layers": [0], "init": "normal"},        # 2 rows, 1 layer
    {"shape": (2, 4), "layers": [1, 1], "init": "normal"},     # a layer twice
    {"shape": (2, 4), "init": "zeros"},                        # not in the set
    {"shape": (2, 4), "init": ["uniform", 0.0]},               # an end missing
])
def test_a_table_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        weights.make_weights({"model.layers.{i}.x.weight": bad}, seed=1)


def _weights_as_before(cfg, seed):
    """``make_weights`` as it was before a table could say how a tensor is
    drawn (PR 24 to PR 27), kept as the loop the new one is held to: every
    tensor of ``weight_shapes`` on every layer, the key split over the sorted
    names, a norm told by its name."""
    import jax
    import jax.numpy as jnp
    shapes = weights.weight_shapes(cfg)
    names = sorted(shapes)

    def build_all(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            x = jax.random.normal(k, shapes[name], jnp.float32)
            is_norm = "norm" in name.rsplit(".", 2)[-2]
            x = (1.0 + weights.NORM_JITTER * x if is_norm
                 else weights.INIT_STD * x)
            out[name] = x.astype(jnp.bfloat16)
        return out

    return jax.jit(build_all)(jax.random.PRNGKey(seed))


@pytest.mark.parametrize("family", ["mistral", "olmoe"])
def test_builtin_gate_weights_are_the_numbers_they_were(family):
    """Same seed, same arrays: the two cells' gates compare what they
    compared before the table (their printed max_error is held equal to the
    parent's on the chip, PERF.md section 6)."""
    cfg = CASES[family]
    seed = 2**31 + 1234
    table = build.load_reference(family).weight_shapes(cfg)
    new = weights.make_weights(table, seed)
    old = _weights_as_before(cfg, seed)
    assert sorted(new) == sorted(old)
    for name in old:
        np.testing.assert_array_equal(np.asarray(new[name]),
                                      np.asarray(old[name]), err_msg=name)
    view = weights.HfView(table, new)
    n_l, n_e = cfg["num_hidden_layers"], cfg.get("num_experts", 0)
    flat = sum((n_l * n_e if "{e}" in k else n_l if "{i}" in k else 1)
               for k in old)
    assert len(view) == flat


def test_an_unknown_model_type_names_the_file_to_add():
    with pytest.raises(FileNotFoundError,
                       match=r"benchmark/references/granitemoehybrid\.py"):
        build.load_reference("granitemoehybrid")
    # found by name under the toy benchmark, not built in
    assert "granite" not in build.BUILTIN_REFERENCES
    assert build.load_reference("granite").__file__.startswith(TOY_ROOT)
