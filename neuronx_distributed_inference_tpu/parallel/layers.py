"""Sharded layer building blocks — TPU-native replacement for the reference's
NxD parallel layers (reference: neuronx_distributed ``parallel_layers``
ColumnParallelLinear / RowParallelLinear / ParallelEmbedding and the GQA
sharding utilities in
src/neuronx_distributed_inference/modules/attention/gqa.py).

Design: under GSPMD there is no "parallel linear module" — a linear layer is a
weight with a PartitionSpec plus a plain ``jnp.einsum``; XLA inserts the
collectives (all-reduce for row-parallel, etc.). What remains of the
reference's parallel-layer machinery is:

  * declaring weight layouts (column vs row sharding)           -> ParamSpec
  * GQA head padding / replication so kv-heads divide tp
    (reference: gqa.py:32-244)                                  -> here
  * checkpoint-time resharding hooks (reference: gqa.py:679+)   -> shape
    transforms applied by utils/checkpoint.py using these specs
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXIS_EP, AXIS_MP, AXIS_TP


@dataclass(frozen=True)
class ParamSpec:
    """Shape + sharding declaration for one weight tensor."""

    shape: Tuple[int, ...]
    pspec: P
    dtype: jnp.dtype = jnp.bfloat16
    # how to initialize for random-weight tests; loaded checkpoints override
    init: str = "normal"   # "normal" | "zeros" | "ones"
    # (axis, width): the tensor is stored wider along ``axis`` than the model
    # is, and everything from ``width`` on is padding, drawn as zeros
    live: Optional[Tuple[int, int]] = None

    def initializer(self, key, scale: float = 0.02):
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        w = jax.random.normal(key, self.shape, jnp.float32) * scale
        if self.live is not None:
            axis, width = self.live
            w = jnp.where(jax.lax.broadcasted_iota(jnp.int32, self.shape, axis)
                          < width, w, 0.0)
        return w.astype(self.dtype)


def column_parallel(in_dim: int, out_dim: int, dtype=jnp.bfloat16,
                    layer_stacked: bool = False, num_layers: int = 0) -> ParamSpec:
    """Weight (in, out) with the OUTPUT dim sharded on the full model-parallel
    axis set ("ep","tp") — the analog of ColumnParallelLinear
    (gather_output=False)."""
    if layer_stacked:
        return ParamSpec((num_layers, in_dim, out_dim), P(None, None, AXIS_MP), dtype)
    return ParamSpec((in_dim, out_dim), P(None, AXIS_MP), dtype)


def row_parallel(in_dim: int, out_dim: int, dtype=jnp.bfloat16,
                 layer_stacked: bool = False, num_layers: int = 0) -> ParamSpec:
    """Weight (in, out) with the INPUT dim sharded on ("ep","tp") — the analog
    of RowParallelLinear (input_is_parallel=True); XLA emits the all-reduce."""
    if layer_stacked:
        return ParamSpec((num_layers, in_dim, out_dim), P(None, AXIS_MP, None), dtype)
    return ParamSpec((in_dim, out_dim), P(AXIS_MP, None), dtype)


def row_parallel_output(x, w, *, collective_dtype: Optional[str] = None,
                        collective_block: int = 32):
    """Compute a row-parallel layer's output: ``x`` (B, T, K) with K sharded
    over ("ep","tp"), ``w`` (K, N) per :func:`row_parallel`.

    With ``collective_dtype`` None this is the classic GSPMD form — a plain
    (q)linear whose all-reduce XLA inserts from the sharding constraints.
    With "int8"/"fp8" the reduction is EXPLICIT: a shard_map ring exchange
    with a quantized wire payload (parallel/collectives.py, EQuARX-style).
    """
    if collective_dtype is None:
        from ..modules.quantization import qlinear
        return qlinear(x, w)
    from . import collectives
    return collectives.quantized_row_parallel(
        x, w, dtype=collective_dtype, block=collective_block)


def vocab_parallel_embedding(vocab: int, hidden: int, dtype=jnp.bfloat16) -> ParamSpec:
    """Embedding (V, H) sharded on V (reference: ParallelEmbedding with
    vocab_parallel, models/config.py:142)."""
    return ParamSpec((vocab, hidden), P(AXIS_MP, None), dtype)


def expert_column_parallel(num_experts: int, in_dim: int, out_dim: int,
                           dtype=jnp.bfloat16, layer_stacked: bool = False,
                           num_layers: int = 0) -> ParamSpec:
    """Expert weight (E, in, out): experts sharded on "ep" (moe_ep), the
    output dim on "tp" (moe_tp) — reference: modules/moe_v2.py:135-161
    moe_tp_degree x moe_ep_degree expert sharding."""
    if layer_stacked:
        return ParamSpec((num_layers, num_experts, in_dim, out_dim),
                         P(None, AXIS_EP, None, AXIS_TP), dtype)
    return ParamSpec((num_experts, in_dim, out_dim),
                     P(AXIS_EP, None, AXIS_TP), dtype)


def expert_row_parallel(num_experts: int, in_dim: int, out_dim: int,
                        dtype=jnp.bfloat16, layer_stacked: bool = False,
                        num_layers: int = 0) -> ParamSpec:
    """Expert weight (E, in, out): experts on "ep", input dim on "tp"."""
    if layer_stacked:
        return ParamSpec((num_layers, num_experts, in_dim, out_dim),
                         P(None, AXIS_EP, AXIS_TP, None), dtype)
    return ParamSpec((num_experts, in_dim, out_dim),
                     P(AXIS_EP, AXIS_TP, None), dtype)


def replicated_param(shape: Tuple[int, ...], dtype=jnp.bfloat16, init="ones") -> ParamSpec:
    return ParamSpec(tuple(shape), P(), dtype, init)


# ---------------------------------------------------------------------------
# GQA head sharding (reference: modules/attention/gqa.py)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GQASharding:
    """Resolved GQA head layout for a given tp degree.

    Strategies (reference: gqa.py:32-101):
      REPLICATE_TO_TP_DEGREE — repeat each KV head so num_kv_heads divides tp
      CONVERT_TO_MHA         — degenerate case rep == q_per_kv
    Plus Q/KV head *padding and reordering* when replication exceeds the
    original q-per-kv ratio (reference: gqa.py:137-244 pads heads and permutes
    Q so each rank holds Q heads together with their KV head).

    Layout invariants used by the attention op (ops/attention.py mha groups
    q heads by ``i // (num_q/num_kv)``):
      * padded KV slot s holds original KV head ``s // kv_replication``
      * original Q head i lives at padded slot ``q_slot_map[i]``; unused
        slots are zero (their o_proj rows are zero too, so they contribute
        nothing).
    """

    num_q_heads: int           # padded logical q heads
    num_kv_heads: int          # padded/replicated logical kv heads
    orig_q_heads: int
    orig_kv_heads: int
    kv_replication: int        # how many times each original kv head is repeated
    tp: int

    @property
    def q_per_kv(self) -> int:
        return self.num_q_heads // self.num_kv_heads

    @property
    def q_slot_map(self) -> Tuple[int, ...]:
        """orig q head i -> padded q slot, preserving kv alignment."""
        orig_qpk = self.orig_q_heads // self.orig_kv_heads
        rep, g = self.kv_replication, self.q_per_kv
        out = []
        for i in range(self.orig_q_heads):
            j, o = divmod(i, orig_qpk)
            out.append(j * rep * g + o)
        return tuple(out)

    @property
    def is_identity(self) -> bool:
        return (self.num_q_heads == self.orig_q_heads
                and self.num_kv_heads == self.orig_kv_heads)


def resolve_gqa_sharding(num_q_heads: int, num_kv_heads: int, tp: int) -> GQASharding:
    """Compute the padded/replicated head layout so kv heads divide tp.

    Mirrors the semantics of gqa.py:62-244. Requires the usual power-of-two
    style divisibility (num_q % num_kv == 0 and tp % num_kv == 0 or
    num_kv % tp == 0) — same constraint set the reference enforces.
    """
    if num_q_heads % num_kv_heads != 0:
        raise ValueError(f"num_q_heads {num_q_heads} must be a multiple of "
                         f"num_kv_heads {num_kv_heads}")
    orig_qpk = num_q_heads // num_kv_heads
    if num_kv_heads % tp == 0:
        rep = 1
        padded_kv = num_kv_heads
        g = orig_qpk
    elif tp % num_kv_heads == 0:
        rep = tp // num_kv_heads
        padded_kv = tp
        g = max(1, -(-orig_qpk // rep))  # ceil
    else:
        raise ValueError(f"unsupported head/tp combination: kv={num_kv_heads} tp={tp}")
    padded_q = padded_kv * g
    return GQASharding(padded_q, padded_kv, num_q_heads, num_kv_heads, rep, tp)


def _to_heads(w: np.ndarray, n_heads: int, head_dim: int, axis: int):
    shape = list(w.shape)
    axis = axis % w.ndim
    assert shape[axis] == n_heads * head_dim, (shape, n_heads, head_dim)
    shape[axis] = n_heads
    shape.insert(axis + 1, head_dim)
    return w.reshape(shape), axis


def _from_heads(w: np.ndarray, axis: int):
    shape = list(w.shape)
    shape[axis] = shape[axis] * shape[axis + 1]
    shape.pop(axis + 1)
    return w.reshape(shape)


def replicate_kv_weight(w: np.ndarray, sharding: GQASharding, head_dim: int,
                        axis: int = -1) -> np.ndarray:
    """Expand a K or V projection weight (..., orig_kv*dh) to the replicated
    layout (..., num_kv*dh): padded slot s = orig head s // rep
    (reference: gqa.py:137-244 ``replicate_kv``)."""
    if sharding.is_identity:
        return w
    w, axis = _to_heads(w, sharding.orig_kv_heads, head_dim, axis)
    w = np.repeat(w, sharding.kv_replication, axis=axis)
    return _from_heads(w, axis)


def place_q_weight(w: np.ndarray, sharding: GQASharding, head_dim: int,
                   axis: int = -1) -> np.ndarray:
    """Scatter original Q heads into their padded slots (zero elsewhere)
    per ``q_slot_map`` (reference: gqa.py head pad + reorder utilities)."""
    if sharding.is_identity:
        return w
    w, axis = _to_heads(w, sharding.orig_q_heads, head_dim, axis)
    out_shape = list(w.shape)
    out_shape[axis] = sharding.num_q_heads
    out = np.zeros(out_shape, dtype=w.dtype)
    idx = [slice(None)] * w.ndim
    src = [slice(None)] * w.ndim
    for i, s in enumerate(sharding.q_slot_map):
        idx[axis] = s
        src[axis] = i
        out[tuple(idx)] = w[tuple(src)]
    return _from_heads(out, axis)
