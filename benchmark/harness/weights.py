"""Seeded weights for the correctness gate, in the published layout.

One jitted call makes every tensor on the device from the seed, already
rounded to bfloat16 (the type the model is served in), under the names and in
the orientation the reference reads (``reference.py``: per-layer tensors
stacked on a leading axis, experts on a second). :class:`HfView` exposes the
same numbers under the flat published checkpoint names, which is what the
program's own loader (``family.convert_hf_state_dict``) takes — so both sides
of the gate hold the same bf16-rounded weights and neither reads the other's
layout.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference import EXPERT, L

INIT_STD = 0.02          # the published initializer_range of both models
NORM_JITTER = 0.1        # norm weights 1 + 0.1 * N(0, 1): a dropped norm shows


def weight_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every tensor of a model with config ``cfg``."""
    n_l, hid, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["vocab_size"])
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or hid // nq
    inter = cfg["intermediate_size"]
    shapes = {
        "model.embed_tokens.weight": (vocab, hid),
        "model.norm.weight": (hid,),
        L + "input_layernorm.weight": (n_l, hid),
        L + "post_attention_layernorm.weight": (n_l, hid),
        L + "self_attn.q_proj.weight": (n_l, nq * d, hid),
        L + "self_attn.k_proj.weight": (n_l, nkv * d, hid),
        L + "self_attn.v_proj.weight": (n_l, nkv * d, hid),
        L + "self_attn.o_proj.weight": (n_l, hid, nq * d),
    }
    if not cfg.get("tie_word_embeddings"):
        shapes["lm_head.weight"] = (vocab, hid)
    if cfg["model_type"] == "olmoe":
        shapes[L + "self_attn.q_norm.weight"] = (n_l, nq * d)
        shapes[L + "self_attn.k_norm.weight"] = (n_l, nkv * d)
    if "num_experts" in cfg:
        n_e = cfg["num_experts"]
        shapes[L + "mlp.gate.weight"] = (n_l, n_e, hid)
        shapes[EXPERT + "gate_proj.weight"] = (n_l, n_e, inter, hid)
        shapes[EXPERT + "up_proj.weight"] = (n_l, n_e, inter, hid)
        shapes[EXPERT + "down_proj.weight"] = (n_l, n_e, hid, inter)
    else:
        shapes[L + "mlp.gate_proj.weight"] = (n_l, inter, hid)
        shapes[L + "mlp.up_proj.weight"] = (n_l, inter, hid)
        shapes[L + "mlp.down_proj.weight"] = (n_l, hid, inter)
    return shapes


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All tensors, bfloat16, on the default device, from one jitted call."""
    shapes = weight_shapes(cfg)
    names = sorted(shapes)

    def build(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            x = jax.random.normal(k, shapes[name], jnp.float32)
            is_norm = "norm" in name.rsplit(".", 2)[-2]
            x = 1.0 + NORM_JITTER * x if is_norm else INIT_STD * x
            out[name] = x.astype(jnp.bfloat16)
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed))


def _to_host(name: str, value, dtype) -> np.ndarray:
    """One tensor on the host, in its published shape. A ``Linear.weight``
    comes over already transposed on the device and is handed out as the
    transposed VIEW of that copy: the loader's first act on it is
    ``np.ascontiguousarray(w.T)``, which numpy does element by element for
    bfloat16 (10 s of a 35 s set-up, my chip run, PR 24) and which is then
    free. Only the memory order differs; the numbers and shapes do not."""
    linear = value.ndim >= 2 and name != "model.embed_tokens.weight"
    host = np.asarray(jnp.swapaxes(value, -1, -2) if linear else value)
    if dtype is not None:
        host = host.astype(dtype)
    return host.swapaxes(-1, -2) if linear else host


class HfView(Mapping):
    """The stacked tensors under the flat published names
    (``model.layers.3.mlp.experts.17.up_proj.weight``), as numpy arrays —
    the state dict the program's loader converts."""

    def __init__(self, cfg: Dict[str, Any], weights: Mapping[str, Any],
                 dtype=None):
        """``dtype``: what to hand the loader where the model is NOT served
        in bfloat16 (the CPU toys are float32): the program's loader leaves a
        bfloat16 numpy array as it is, whatever dtype the model asks for."""
        self._index: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
        self._host = {k: _to_host(k, v, dtype) for k, v in weights.items()}
        n_l, n_e = cfg["num_hidden_layers"], cfg.get("num_experts", 0)
        for name in weights:
            if "{e}" in name:
                for i in range(n_l):
                    for e in range(n_e):
                        self._index[name.format(i=i, e=e)] = (name, (i, e))
            elif "{i}" in name:
                for i in range(n_l):
                    self._index[name.format(i=i)] = (name, (i,))
            else:
                self._index[name] = (name, ())

    def __getitem__(self, key: str) -> np.ndarray:
        name, at = self._index[key]
        return self._host[name][at]

    def __iter__(self) -> Iterator[str]:
        return iter(self._index)

    def __len__(self) -> int:
        return len(self._index)
