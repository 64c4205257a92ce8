#!/usr/bin/env python
"""Decompose decode-step time on the real chip: full step vs layers-only vs
lm_head-only vs sampling-only, each amortized over N in-graph iterations so
the host's dispatch and fetch cost doesn't pollute the numbers."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.llama import LlamaInferenceConfig
from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.modules.kv_cache import KVCacheSpec, init_cache
from neuronx_distributed_inference_tpu.parallel.mesh import MeshConfig, build_mesh

batch, seq_len = 2, 1024
hf_attrs = dict(
    model_type="llama", hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
    head_dim=64, vocab_size=128256, rms_norm_eps=1e-5, rope_theta=500000.0,
    hidden_act="silu", tie_word_embeddings=True,
)
tcfg = TpuConfig(batch_size=batch, seq_len=seq_len, max_context_length=128,
                 dtype="bfloat16", enable_bucketing=False)
icfg = LlamaInferenceConfig(tcfg, **hf_attrs)
mesh = build_mesh(MeshConfig())
spec = model_base.spec_from_config(icfg)
params = model_base.init_params(spec, jax.random.PRNGKey(0), mesh)
kvspec = KVCacheSpec(spec.num_layers, batch, seq_len, spec.gqa.num_kv_heads,
                     spec.head_dim)
cache = init_cache(kvspec, mesh)

N1, N2 = 16, 80


def _scalarize(out):
    leaves = jax.tree.leaves(out)
    return sum(jnp.sum(x).astype(jnp.float32) for x in leaves)


def timed(name, make_fn, *args):
    """make_fn(n) -> jitted fn running n iterations; returns a scalar.
    The scalar fetch (np.asarray) returns only when the chip has finished,
    as block_until_ready would; the slope between two iteration counts
    cancels the fixed dispatch + fetch cost."""
    fns = {n: make_fn(n) for n in (N1, N2)}
    for n, fn in fns.items():
        np.asarray(fn(*args))  # compile + warm
    t = {}
    for n, fn in fns.items():
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(fn(*args))
            reps.append(time.perf_counter() - t0)
        t[n] = min(reps)
    per_step = (t[N2] - t[N1]) / (N2 - N1) * 1e3
    print(f"{name:30s} {per_step:8.3f} ms/step   (t{N1}={t[N1]*1e3:.1f}ms t{N2}={t[N2]*1e3:.1f}ms)")
    return per_step


def make_full_loop(n):
    def full_loop(params, cache):
        def step(carry, _):
            tok, pos, cch = carry
            out = model_base.token_generation_step(
                spec, tcfg, params, cch, tok[:, None], pos[:, None],
                jnp.arange(batch), None, jax.random.PRNGKey(0))
            return (out["tokens"], pos + 1, out["cache"]), None
        (tok, _, c), _ = jax.lax.scan(
            step, (jnp.zeros((batch,), jnp.int32),
                   jnp.full((batch,), 128, jnp.int32), cache), None, length=n)
        return tok.sum()
    return jax.jit(full_loop)


def make_layers_only(n):
    def layers_only(params, cache):
        def step(carry, _):
            h_sum, pos, cch = carry
            ai = model_base.attn_inputs(
                spec, pos[:, None],
                lambda w, c: jnp.ones((batch, 1, seq_len), bool))
            hidden = model_base._embed(spec, params,
                                       jnp.zeros((batch, 1), jnp.int32))
            hidden, new_cache, _ = model_base.run_layers(
                spec, params, cch, hidden, ai, jnp.arange(batch),
                pos[:, None], "decode", identity_seq_ids=True)
            return (h_sum + hidden.sum(), pos + 1, new_cache), None
        (s, _, c), _ = jax.lax.scan(
            step, (jnp.zeros((), jnp.bfloat16),
                   jnp.full((batch,), 128, jnp.int32), cache), None, length=n)
        return s.astype(jnp.float32)
    return jax.jit(layers_only)


def make_lm_head_only(n):
    def lm_head_only(params, cache):
        def step(carry, _):
            h = carry
            logits = model_base._lm_head(spec, params, h)
            return h + logits.max(axis=-1).astype(h.dtype)[..., None] * 1e-9, None
        h0 = jnp.ones((batch, 1, spec.hidden_size), jnp.bfloat16)
        h, _ = jax.lax.scan(step, h0, None, length=n)
        return h.sum().astype(jnp.float32)
    return jax.jit(lm_head_only)


def make_attn_only(n):
    from neuronx_distributed_inference_tpu.ops import attention as attn_ops
    from neuronx_distributed_inference_tpu.modules import kv_cache as kvm
    def attn_only(params, cache):
        def step(carry, _):
            acc, cch = carry
            acc2 = acc
            for li in range(spec.num_layers):  # decode unrolls layers too
                k_layer = kvm.read_layer_hl(cch["k"], li)   # (B, H, D, S)
                v_layer = kvm.read_layer_hl(cch["v"], li)   # (B, H, S, D)
                q = jnp.full((batch, 1, spec.gqa.num_q_heads, spec.head_dim),
                             acc2 * 1e-9 + 1.0, jnp.bfloat16)
                o = attn_ops.mha_hl(q, k_layer, v_layer, None, spec.scale)
                acc2 = acc2 + o.sum().astype(jnp.float32)
            return (acc2, cch), None
        (s, _), _ = jax.lax.scan(step, (jnp.zeros((), jnp.float32), cache),
                                 None, length=n)
        return s
    return jax.jit(attn_only)


def make_stream(n):
    def stream(params, cache):
        def body(acc, _):
            s = sum(jnp.sum(x * (1.0 + acc * 1e-30)).astype(jnp.float32)
                    for x in jax.tree.leaves(params))
            return acc + s, None
        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None, length=n)
        return acc
    return jax.jit(stream)


t_full = timed("full decode step", make_full_loop, params, cache)
t_layers = timed("layers only", make_layers_only, params, cache)
t_head = timed("lm_head only", make_lm_head_only, params, cache)
t_attn = timed("attention-over-cache only", make_attn_only, params, cache)
psize = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
t_stream = timed("param sum (pure stream)", make_stream, params, cache)
print(f"param bytes {psize/1e9:.3f} GB")
print(f"implied stream BW {psize/1e9/t_stream*1e3:.0f} GB/s")
print(f"full-step implied BW {psize/1e9/t_full*1e3:.0f} GB/s")
