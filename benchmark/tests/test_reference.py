"""``reference.py`` against the HF implementations in float32 at a toy size."""

import numpy as np
import pytest

from harness import reference, weights

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


def _hf_model(cfg):
    import torch
    import transformers
    torch.manual_seed(0)
    kw = {k: v for k, v in cfg.items() if k != "model_type"}
    if cfg["model_type"] == "olmoe":
        model = transformers.OlmoeForCausalLM(transformers.OlmoeConfig(**kw))
    else:
        model = transformers.MistralForCausalLM(
            transformers.MistralConfig(**kw))
    with torch.no_grad():           # norm weights away from 1, as in the gate
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.1 * torch.randn_like(p))
    return model.float().eval()


def _stacked(cfg, sd):
    """HF's flat state dict -> the reference's stacked names."""
    out = {}
    for name, shape in weights.weight_shapes(cfg).items():
        def get(**at):
            return sd[name.format(**at)].numpy()
        if "{e}" in name:
            arr = np.stack([np.stack([get(i=i, e=e)
                                      for e in range(cfg["num_experts"])])
                            for i in range(cfg["num_hidden_layers"])])
        elif "{i}" in name:
            arr = np.stack([get(i=i)
                            for i in range(cfg["num_hidden_layers"])])
        else:
            arr = get()
        assert arr.shape == shape, (name, arr.shape, shape)
        out[name] = arr
    return out


@pytest.mark.parametrize("family", sorted(CASES))
def test_reference_matches_hf(family):
    import jax
    import torch
    cfg = CASES[family]
    model = _hf_model(cfg)
    w = _stacked(cfg, model.state_dict())
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 24))
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.forward(cfg, w, ids))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("family", sorted(CASES))
def test_hfview_round_trips_the_generated_weights(family):
    cfg = CASES[family]
    w = weights.make_weights(cfg, seed=2**31 + 7)
    assert {k: v.shape for k, v in w.items()} == weights.weight_shapes(cfg)
    view = weights.HfView(cfg, w)
    again = _stacked(cfg, {k: _Torchish(v) for k, v in view.items()})
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
