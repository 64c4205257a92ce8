"""On-device sampling (reference: modules/generation/sampling.py ``Sampler``).

Everything runs inside the decode graph: greedy argmax, or
top-k / top-p / temperature multinomial with **per-request** sampling params
(reference: prepare_sampling_params :183 — a (B, 3) tensor of
[top_k, top_p, temperature]).

The reference implements a multi-stage hierarchical top-k because Neuron lacks
a fast full-vocab sort (:285-335). On TPU, ``jax.lax.top_k`` with a static
``global_topk`` bound (default 256) plays the same role: one top_k over the
vocab shard, then per-request masking down to the dynamic k.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import OnDeviceSamplingConfig


def prepare_sampling_params(batch_size: int, top_k=1, top_p=1.0, temperature=1.0):
    """Host helper -> (B, 3) fp32 [top_k, top_p, temperature]
    (reference: sampling.py:183 ``prepare_sampling_params``)."""
    import numpy as np

    def _bcast(v):
        a = np.asarray(v, dtype=np.float32).reshape(-1)
        if a.size == 1:
            a = np.full((batch_size,), a[0], dtype=np.float32)
        if a.size != batch_size:
            raise ValueError(f"sampling param batch {a.size} != {batch_size}")
        return a

    return np.stack([_bcast(top_k), _bcast(top_p), _bcast(temperature)], axis=1)


def mask_padded_logits(logits: jnp.ndarray, pad_size: int) -> jnp.ndarray:
    """Mask vocab-padding columns added for tp divisibility
    (reference: sampling.py:24 ``mask_padded_logits``)."""
    if pad_size == 0:
        return logits
    v = logits.shape[-1]
    col = jnp.arange(v) >= (v - pad_size)
    return jnp.where(col, jnp.finfo(logits.dtype).min, logits)


def greedy_sample(logits: jnp.ndarray) -> jnp.ndarray:
    """(…, V) -> (…,) int32 argmax (reference: nxd argmax op path)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def truncated_probs(logits: jnp.ndarray, sampling_params: jnp.ndarray,
                    global_topk: int = 256):
    """The shared top-k/top-p/temperature truncation: logits (B, V) +
    sampling_params (B, 3) -> (probs, top_idx), both (B, k) with
    k = min(global_topk, V), probs renormalized over the kept prefix.
    top_k <= 0 or >= global_topk means "no k truncation beyond
    global_topk"."""
    b, v = logits.shape
    k = min(global_topk, v)
    lf = logits.astype(jnp.float32)
    top_vals, top_idx = jax.lax.top_k(lf, k)  # (B, k) sorted desc

    req_k = sampling_params[:, 0]
    req_p = sampling_params[:, 1]
    temp = jnp.maximum(sampling_params[:, 2], 1e-6)

    ranks = jnp.arange(k, dtype=jnp.float32)[None, :]
    kmask = jnp.where(req_k[:, None] > 0, ranks < req_k[:, None], True)

    scaled = top_vals / temp[:, None]
    probs = jax.nn.softmax(jnp.where(kmask, scaled, -jnp.inf), axis=-1)
    # top-p: keep the smallest prefix of sorted probs with cumsum >= p,
    # always keeping the top token.
    cum = jnp.cumsum(probs, axis=-1)
    pmask = (cum - probs) < req_p[:, None]
    probs = jnp.where(pmask & kmask, probs, 0.0)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return probs, top_idx


def topk_topp_sample(logits: jnp.ndarray, sampling_params: jnp.ndarray,
                     key: jax.Array, global_topk: int = 256,
                     deterministic: bool = False) -> jnp.ndarray:
    """Per-request top-k/top-p/temperature sampling.

    logits (B, V); sampling_params (B, 3) = [top_k, top_p, temperature].
    """
    probs, top_idx = truncated_probs(logits, sampling_params, global_topk)
    if deterministic:
        choice = jnp.argmax(probs, axis=-1)
    else:
        # gumbel-max over the truncated distribution
        g = jax.random.gumbel(key, probs.shape, dtype=jnp.float32)
        choice = jnp.argmax(jnp.where(probs > 0, jnp.log(probs) + g, -jnp.inf), axis=-1)
    return jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


def stream_keys(stream_seed: int, seeds: jnp.ndarray,
                positions: jnp.ndarray) -> jax.Array:
    """Per-draw PRNG keys for the positionally coupled stream: row i's
    key is ``fold_in(fold_in(PRNGKey(stream_seed), seeds[i]),
    positions[i])`` — a pure function of (engine stream seed, request
    seed, absolute position of the token whose logits are sampled), so
    the same draw falls out of ANY graph that samples that position:
    eager decode, the fused decode loop, the prefill tail, a draft-loop
    step, a verify column, or a ragged verify row."""
    base = jax.random.PRNGKey(stream_seed)
    return jax.vmap(lambda s, p: jax.random.fold_in(
        jax.random.fold_in(base, s), p))(
        seeds.astype(jnp.int32), positions.astype(jnp.int32))


@jax.named_scope("sample")
def coupled_sample(logits: jnp.ndarray,
                   config: OnDeviceSamplingConfig,
                   sampling_params: Optional[jnp.ndarray],
                   seeds: jnp.ndarray,
                   positions: jnp.ndarray) -> jnp.ndarray:
    """Positionally coupled top-k/top-p/temperature sampling.

    Unlike :func:`sample` (one gumbel block per dispatch, so streams
    depend on scheduling), every draw here is keyed by
    :func:`stream_keys` and the per-row gumbel noise has a fixed shape
    (k,), making the sampled token a pure function of (stream_seed,
    request seed, position, logits). That invariance is what makes
    gumbel-coupled rejection sampling exact: the verify graph's coupled
    draw at position p IS the token eager decode would have sampled at
    p, so accept-by-exact-match preserves both the output distribution
    and the stream (see README "Sampled speculation & compressed
    decode").

    logits (B, V) with seeds (B,) / positions (B,), or (B, T, V) with
    positions (B, T); sampling_params (B, 3) or None (config-static).
    """
    squeeze = False
    if logits.ndim == 3:
        b, t, v = logits.shape
        logits = logits.reshape(b * t, v)
        seeds = jnp.broadcast_to(seeds[:, None], (b, t)).reshape(-1)
        positions = positions.reshape(-1)
        if sampling_params is not None and sampling_params.shape[0] == b:
            sampling_params = jnp.repeat(sampling_params, t, axis=0)
        squeeze = (b, t)
    if sampling_params is None:
        sampling_params = jnp.broadcast_to(
            jnp.array([[config.top_k, config.top_p, config.temperature]],
                      jnp.float32), (logits.shape[0], 3))
    probs, top_idx = truncated_probs(logits, sampling_params,
                                     config.global_topk)
    if config.deterministic:
        choice = jnp.argmax(probs, axis=-1)
    else:
        keys = stream_keys(config.stream_seed or 0, seeds, positions)
        kwidth = probs.shape[-1]
        g = jax.vmap(lambda k: jax.random.gumbel(k, (kwidth,),
                                                 jnp.float32))(keys)
        choice = jnp.argmax(jnp.where(probs > 0, jnp.log(probs) + g,
                                      -jnp.inf), axis=-1)
    toks = jnp.take_along_axis(top_idx, choice[:, None],
                               axis=-1)[:, 0].astype(jnp.int32)
    if squeeze:
        toks = toks.reshape(squeeze)
    return toks


@jax.named_scope("sample")      # sample_dp lands here too: the one scope
def sample(logits: jnp.ndarray, config: Optional[OnDeviceSamplingConfig],
           sampling_params: Optional[jnp.ndarray] = None,
           key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Dispatch greedy vs multinomial; (B, V) or (B, T, V) logits -> tokens."""
    squeeze = False
    if logits.ndim == 3:
        b, t, v = logits.shape
        logits = logits.reshape(b * t, v)
        squeeze = (b, t)
    if sampling_params is None and (config is None or not config.do_sample):
        toks = greedy_sample(logits)
    elif sampling_params is None:
        sp = jnp.broadcast_to(
            jnp.array([[config.top_k, config.top_p, config.temperature]],
                      jnp.float32), (logits.shape[0], 3))
        toks = topk_topp_sample(logits, sp, key, config.global_topk,
                                config.deterministic)
    else:
        if sampling_params.shape[0] != logits.shape[0]:
            sampling_params = jnp.repeat(
                sampling_params, logits.shape[0] // sampling_params.shape[0], axis=0)
        toks = topk_topp_sample(logits, sampling_params, key,
                                config.global_topk if config else 256,
                                config.deterministic if config else False)
    if squeeze:
        toks = toks.reshape(squeeze)
    return toks


def sample_dp(logits: jnp.ndarray, config, sampling_params, key,
              mesh=None) -> jnp.ndarray:
    """Batch-sharded sampling (reference: modules/generation/sampling.py
    :467-578 ``DataParallelSampler``): shard_map :func:`sample` over the
    mesh "dp" axis so each shard runs top-k on its own batch slice —
    the (B, V) logits are never gathered. Falls back to the global
    :func:`sample` when no dp axis is active or B doesn't divide."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    b = logits.shape[0]
    if ("dp" not in mesh.axis_names
            or mesh.shape["dp"] <= 1 or b % mesh.shape["dp"] != 0):
        return sample(logits, config, sampling_params, key)
    from jax.sharding import PartitionSpec as P
    dp = mesh.shape["dp"]
    specs = [P("dp")]
    args = [logits]
    if sampling_params is not None:
        specs.append(P("dp") if sampling_params.shape[0] == b else P())
        args.append(sampling_params)
    if key is not None:
        # fold the shard index into the key so shards draw independent noise
        specs.append(P())
        args.append(key)

    def body(lg, *rest):
        sp = rest[0] if sampling_params is not None else None
        k = rest[-1] if key is not None else None
        if k is not None:
            k = jax.random.fold_in(k, jax.lax.axis_index("dp"))
        return sample(lg, config, sp, k)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                         out_specs=P("dp"), check_vma=False)(*args)
