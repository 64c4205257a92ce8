"""Seeded weights for the correctness gate, in the published layout.

One jitted call makes every tensor on the device from the seed, already
rounded to bfloat16 (the type the model is served in), under the names and in
the orientation the reference reads (``reference.py``: per-layer tensors
stacked on a leading axis, experts on a second). :class:`HfView` exposes the
same numbers under the flat published checkpoint names, which is what the
program's own loader (``family.convert_hf_state_dict``) takes — so both sides
of the gate hold the same bf16-rounded weights and neither reads the other's
layout.

Which tensors there are is a **table**, ``name -> entry``, that the
architecture's reference gives (``weight_shapes(cfg)`` of
``references/<model_type>.py``; :func:`weight_table` for the two types
``reference.py`` covers). An entry is a dict:

* ``shape``: of the stacked array. A name with ``{i}`` in it is stacked over
  the layers that carry it on its first axis, one with ``{e}`` over the
  experts on its second;
* ``layers`` (optional): the indices of the layers that carry a ``{i}``
  tensor, in the order of the first axis — a mixer's tensors exist on the
  mixer layers only. Left out: every layer, ``range(shape[0])``;
* ``init``: how it is drawn, one of :data:`INITS` — ``"normal"``
  (``N(0, INIT_STD)``), ``"norm"`` (``1 + NORM_JITTER * N(0, 1)``),
  ``"ones"``, ``["uniform", lo, hi]``, ``["log_uniform", lo, hi]`` (the
  exponential of a uniform draw between ``log lo`` and ``log hi``: a decay or
  a step size whose published initialiser spans decades).
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference import EXPERT, L

INIT_STD = 0.02          # the published initializer_range of both models
NORM_JITTER = 0.1        # norm weights 1 + 0.1 * N(0, 1): a dropped norm shows
#: the closed set of initialisers an entry of a table may name, and how many
#: numbers each takes after its name
INITS = {"normal": 0, "norm": 0, "ones": 0, "uniform": 2, "log_uniform": 2}


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


def weight_table(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """:func:`weight_shapes` as the table a reference gives: every tensor on
    every layer, a norm's weight (by its published name) jittered around one
    and everything else ``N(0, INIT_STD)``."""
    return {name: {"shape": shape,
                   "init": ("norm" if "norm" in name.rsplit(".", 2)[-2]
                            else "normal")}
            for name, shape in weight_shapes(cfg).items()}


def _draw(key, shape: Tuple[int, ...], init) -> jax.Array:
    """One float32 tensor drawn as ``init`` says."""
    kind, *args = [init] if isinstance(init, str) else list(init)
    if INITS.get(kind) != len(args):
        raise ValueError(f"unknown initialiser {init!r}; known: normal, norm, "
                         "ones, [uniform, lo, hi], [log_uniform, lo, hi]")
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, *args)
    if kind == "log_uniform":
        lo, hi = (float(np.log(a)) for a in args)
        return jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    x = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + NORM_JITTER * x if kind == "norm" else INIT_STD * x


def layers_of(name: str, entry: Dict[str, Any]) -> List[int]:
    """The layers that carry the ``{i}`` tensor ``name``, in the order of its
    first axis."""
    n = entry["shape"][0]
    layers = list(entry["layers"]) if entry.get("layers") is not None \
        else list(range(n))
    if len(layers) != n or len(set(layers)) != n:
        raise ValueError(f"{name}: {n} stacked layers but 'layers' is "
                         f"{layers}")
    return layers


def make_weights(table: Mapping[str, Dict[str, Any]],
                 seed: int) -> Dict[str, jax.Array]:
    """All tensors of ``table``, bfloat16, on the default device, from one
    jitted call. The key is split over the sorted names, so a table's numbers
    depend on its names, shapes and initialisers and on nothing else."""
    names = sorted(table)
    for name in names:
        if "{i}" in name:
            layers_of(name, table[name])

    def build(key):
        return {name: _draw(k, tuple(table[name]["shape"]),
                            table[name]["init"]).astype(jnp.bfloat16)
                for name, k in zip(names, jax.random.split(key, len(names)))}

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
    the state dict the program's loader converts. A ``{i}`` tensor appears on
    the layers its table entry names and on no other."""

    def __init__(self, table: Mapping[str, Dict[str, Any]],
                 weights: Mapping[str, Any], dtype=None):
        """``dtype``: what to hand the loader where the model is NOT served
        in bfloat16 (the CPU toys are float32): the program's loader leaves a
        bfloat16 numpy array as it is, whatever dtype the model asks for."""
        self._index: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
        self._host = {k: _to_host(k, v, dtype) for k, v in weights.items()}
        for name in weights:
            if "{i}" not in name:
                self._index[name] = (name, ())
                continue
            experts = (range(table[name]["shape"][1]) if "{e}" in name
                       else [None])
            for row, i in enumerate(layers_of(name, table[name])):
                for e in experts:
                    at = (row,) if e is None else (row, e)
                    self._index[name.format(i=i, e=e)] = (name, at)

    def __getitem__(self, key: str) -> np.ndarray:
        name, at = self._index[key]
        return self._host[name][at]

    def __iter__(self) -> Iterator[str]:
        return iter(self._index)

    def __len__(self) -> int:
        return len(self._index)
