"""Quantized decode collectives — EQuARX-style (PAPERS.md, arxiv 2506.17615)
int8/fp8 ring all-reduce / reduce-scatter for the tp decode collectives.

Under the ring cost model decode is comm-bound (a model, not a chip timing):
the row-parallel all-reduce after ``o_proj`` / ``down_proj`` moves fp32 wire
bytes every step. This module replaces that implicit GSPMD all-reduce with an
EXPLICIT ``shard_map`` two-phase ring exchange whose per-hop payload is
quantized to int8 (qmax 127) or fp8 e4m3 (qmax 448) with blockwise absmax
scales — the same scale plumbing as :mod:`..modules.quantization`
(``quantize_tensor``'s blockwise layout), applied to activations along the
wire instead of weights in HBM:

  phase 1 (reduce-scatter ring): split the local partial sum into ``g``
    chunks; g-1 hops of quantize -> ``ppermute`` -> dequantize -> accumulate;
    device r ends owning the fully-reduced chunk r.
  phase 2 (all-gather ring): circulate the owned chunk's QUANTIZED form
    (quantize once — the payload never changes, so requantization error does
    not compound) for another g-1 hops.

Wire bytes per device: 2(g-1)/g * N bytes at 1 byte/elem vs the fp32 ring
all-reduce's 2(g-1)/g * N * 4 — a 4x reduction, visible in the observatory
census as ``collective-permute`` ops with s8/f8e4m3fn payloads (plus small
fp32 scale permutes) instead of one f32 ``all-reduce``.

Accumulation stays full precision on-device; only the wire payload is
quantized. The knob lives in :class:`..config.CollectiveConfig` and threads
through ``DecoderSpec`` — when off, model graphs contain no shard_map and are
bit-identical to the fp32-collective stream.
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..resilience.errors import ConfigurationError
from .mesh import AXIS_CP, AXIS_DP, AXIS_MP

# dtype knob values -> (wire dtype, symmetric qmax). qmax values match the
# weight-quantization stack (modules/quantization.py quantize_tensor).
WIRE_DTYPES = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}
SUPPORTED_DTYPES = tuple(WIRE_DTYPES)

DEFAULT_BLOCK = 32


def require_supported_dtype(dtype: str) -> None:
    """Typed refusal for unsupported wire dtypes (error-paths contract)."""
    if dtype not in WIRE_DTYPES:
        raise ConfigurationError(
            f"unsupported collective dtype {dtype!r}: quantized collectives "
            f"support {sorted(WIRE_DTYPES)} (None disables)")


def _quantize_wire(x: jnp.ndarray, dtype: str, block: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise symmetric quantize along the last dim.

    Mirrors quantize_tensor's BLOCKWISE layout: one fp32 absmax scale per
    ``block`` contiguous elements. Returns (q (..., C), scale (..., C//block)).
    """
    wire_dtype, qmax = WIRE_DTYPES[dtype]
    *lead, c = x.shape
    grouped = x.astype(jnp.float32).reshape(*lead, c // block, block)
    amax = jnp.max(jnp.abs(grouped), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    scaled = grouped / scale
    if wire_dtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        q = scaled.astype(wire_dtype)
    return q.reshape(*lead, c), scale[..., 0]


def _dequantize_wire(q: jnp.ndarray, scale: jnp.ndarray, block: int,
                     out_dtype) -> jnp.ndarray:
    *lead, c = q.shape
    grouped = q.astype(jnp.float32).reshape(*lead, c // block, block)
    return (grouped * scale[..., None]).reshape(*lead, c).astype(out_dtype)


def _resolve_block(chunk: int, block: int) -> int:
    blk = min(block, chunk)
    if blk < 1 or chunk % blk != 0:
        raise ConfigurationError(
            f"collective block size {block} does not tile the per-shard ring "
            f"chunk of {chunk} elements; pick a block dividing the chunk")
    return blk


def quantized_all_reduce(x: jnp.ndarray, axis_name, group_size: int, *,
                         dtype: str = "int8", block: int = DEFAULT_BLOCK
                         ) -> jnp.ndarray:
    """Two-phase quantized ring all-reduce over ``axis_name``.

    A shard_map collective: call from inside ``jax.shard_map`` where
    ``axis_name`` is live. ``x`` is the local partial sum; the last dim is
    split into ``group_size`` ring chunks (must divide evenly).
    """
    require_supported_dtype(dtype)
    g = int(group_size)
    if g <= 1:
        return x
    n = x.shape[-1]
    if n % g != 0:
        raise ConfigurationError(
            f"quantized all-reduce needs the reduced dim ({n}) divisible by "
            f"the ring group size ({g})")
    blk = _resolve_block(n // g, block)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    r = jax.lax.axis_index(axis_name)
    # (g, ..., chunk): chunk c of the local partial sum at index c
    blocks = jnp.stack(jnp.split(x, g, axis=-1), axis=0)
    # reduce-scatter ring: start from chunk (r-1) so device r ends owning
    # the fully-reduced chunk r after g-1 hops
    cur = jnp.take(blocks, (r - 1) % g, axis=0)
    for step in range(g - 1):
        q, scale = _quantize_wire(cur, dtype, blk)
        q = jax.lax.ppermute(q, axis_name, fwd)
        scale = jax.lax.ppermute(scale, axis_name, fwd)
        recv = _dequantize_wire(q, scale, blk, x.dtype)
        cur = recv + jnp.take(blocks, (r - step - 2) % g, axis=0)
    # all-gather ring: quantize the owned reduced chunk ONCE, forward the
    # quantized payload g-1 hops; own chunk stays full precision locally
    out = jnp.zeros_like(blocks)
    out = out.at[r].set(cur)
    q, scale = _quantize_wire(cur, dtype, blk)
    for step in range(g - 1):
        q = jax.lax.ppermute(q, axis_name, fwd)
        scale = jax.lax.ppermute(scale, axis_name, fwd)
        out = out.at[(r - step - 1) % g].set(
            _dequantize_wire(q, scale, blk, x.dtype))
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], n)


def quantized_reduce_scatter(x: jnp.ndarray, axis_name, group_size: int, *,
                             dtype: str = "int8", block: int = DEFAULT_BLOCK
                             ) -> jnp.ndarray:
    """Quantized ring reduce-scatter over the last dim: device r returns the
    fully-reduced chunk r, shape ``(..., n // group_size)``."""
    require_supported_dtype(dtype)
    g = int(group_size)
    n = x.shape[-1]
    if g <= 1:
        return x
    if n % g != 0:
        raise ConfigurationError(
            f"quantized reduce-scatter needs the reduced dim ({n}) divisible "
            f"by the ring group size ({g})")
    blk = _resolve_block(n // g, block)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    r = jax.lax.axis_index(axis_name)
    blocks = jnp.stack(jnp.split(x, g, axis=-1), axis=0)
    cur = jnp.take(blocks, (r - 1) % g, axis=0)
    for step in range(g - 1):
        q, scale = _quantize_wire(cur, dtype, blk)
        q = jax.lax.ppermute(q, axis_name, fwd)
        scale = jax.lax.ppermute(scale, axis_name, fwd)
        recv = _dequantize_wire(q, scale, blk, x.dtype)
        cur = recv + jnp.take(blocks, (r - step - 2) % g, axis=0)
    return cur


# ---------------------------------------------------------------------------
# Row-parallel entry point (called from traced model code)
# ---------------------------------------------------------------------------

def _live_axes(mesh, names) -> Tuple[str, ...]:
    return tuple(a for a in names
                 if a in mesh.axis_names and mesh.shape[a] > 1)


def _weight_leaf(w: Any):
    """Normalize a row-parallel weight into (shard_map arg, spec, matmul fn).

    Returns None when the leaf cannot be sharded along its contraction dim
    (MXFP4's packed nibbles, or blockwise scales that don't tile the shard) —
    caller falls back to the implicit fp32 collective.
    """
    from ..modules.quantization import is_quantized_leaf, qlinear

    if not is_quantized_leaf(w):
        return w, P(AXIS_MP, None), qlinear
    qw, scale = w["qweight"], w["scale"]
    if qw.dtype == jnp.uint8:       # MXFP4: two fp4 values per byte along K
        return None
    if scale.ndim >= 2 and scale.shape[-2] > 1:
        # blockwise: scale rows tile K; sharding both along the contraction
        # axis stays consistent only when the mesh extent divides the rows
        spec = {"qweight": P(AXIS_MP, None), "scale": P(AXIS_MP, None)}
    else:
        spec = {"qweight": P(AXIS_MP, None), "scale": P(None, None)}
    return w, spec, qlinear


def quantized_row_parallel(x: jnp.ndarray, w: Any, *, dtype: str,
                           block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Row-parallel matmul with a quantized ring all-reduce on the output.

    ``x`` is (B, T, K) with K sharded over the model-parallel axes and B over
    dp; ``w`` is (K, N) row-parallel (fp array or int8/fp8 quantized leaf).
    Falls back to the plain implicit-collective matmul when no model-parallel
    axis is live (single-device graphs stay collective-free) or the weight
    layout cannot shard along K.
    """
    from ..modules.quantization import qlinear

    require_supported_dtype(dtype)
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return qlinear(x, w)
    mp_axes = _live_axes(mesh, AXIS_MP)
    g = math.prod(mesh.shape[a] for a in mp_axes)
    if g <= 1 or x.ndim != 3:
        return qlinear(x, w)
    leaf = _weight_leaf(w)
    if leaf is None:
        return qlinear(x, w)
    w_arg, w_spec, matmul = leaf
    k = x.shape[-1]
    qw = w["qweight"] if isinstance(w, dict) else w
    n = qw.shape[-1]
    if k % g != 0 or n % g != 0:
        raise ConfigurationError(
            f"quantized collectives need the contraction dim ({k}) and the "
            f"output dim ({n}) divisible by the model-parallel extent ({g})")
    if isinstance(w, dict) and isinstance(w_spec, dict):
        srows = w["scale"].shape[-2]
        if w_spec["scale"][0] is not None and srows % g != 0:
            return qlinear(x, w)     # blockwise scale rows don't tile shards
    _resolve_block(n // g, block)    # refuse un-tileable blocks before tracing
    # decode batch shards over (dp, cp) — mirror shard_batch_spec, but only
    # when the batch extent actually divides (otherwise replicate)
    batch_axes = tuple(a for a in _live_axes(mesh, (AXIS_DP, AXIS_CP))
                       if x.shape[0] % mesh.shape[a] == 0)
    dp_spec = batch_axes if batch_axes else None

    def body(xl, wl):
        partial = matmul(xl, wl)
        return quantized_all_reduce(partial, mp_axes, g,
                                    dtype=dtype, block=block)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, mp_axes), w_spec),
        out_specs=P(dp_spec, None, None),
        check_vma=False)(x, w_arg)
