#!/usr/bin/env python
"""Micro-bench: ragged paged decode kernel vs the XLA gather path on the
real chip (show the kernel beating the gather path at
max_blocks >= 4x live length). Prints one JSON line per configuration."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.modules import block_kv_cache as bkv
from neuronx_distributed_inference_tpu.ops import attention as attn_ops
from neuronx_distributed_inference_tpu.ops import decode_attention as da

L, B, HQ, HKV, D, BS = 4, 2, 32, 8, 64, 128


def run(live, mb, iters=64):
    n = 1 + B * mb
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.standard_normal((L, n, BS, HKV, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((L, n, BS, HKV, D)), jnp.bfloat16)
    table = np.zeros((B, mb), np.int32)
    perm = rng.permutation(n - 1) + 1
    for i in range(B):
        table[i, :mb] = perm[i * mb:(i + 1) * mb]
    table = jnp.asarray(table)
    lens = jnp.full((B,), live, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, HQ, D)), jnp.bfloat16)
    nk = jnp.asarray(rng.standard_normal((B, HKV, D)), jnp.bfloat16)
    nv = jnp.asarray(rng.standard_normal((B, HKV, D)), jnp.bfloat16)
    scale = D ** -0.5

    def kernel_loop(n_it):
        def body(acc, _):
            out = 0.0
            for li in range(L):
                o = da.paged_decode_attention(
                    q + acc * 1e-9, kp, vp, nk, nv,
                    jnp.asarray(li, jnp.int32), lens, table, scale=scale)
                out = out + o.sum().astype(jnp.float32)
            return acc + out, None
        return jax.jit(lambda: jax.lax.scan(body, jnp.zeros(()), None,
                                            length=n_it)[0])

    def gather_loop(n_it):
        positions = lens[:, None]
        mask = attn_ops.decode_mask(positions, mb * BS)
        def body(acc, _):
            out = 0.0
            for li in range(L):
                k_all = bkv.gather_layer_kv(kp, li, table)
                v_all = bkv.gather_layer_kv(vp, li, table)
                rows = jnp.arange(B)
                k_all = k_all.at[rows, lens].set(nk)
                v_all = v_all.at[rows, lens].set(nv)
                o = attn_ops.mha((q + acc * 1e-9)[:, None], k_all, v_all,
                                 mask, scale)
                out = out + o.sum().astype(jnp.float32)
            return acc + out, None
        return jax.jit(lambda: jax.lax.scan(body, jnp.zeros(()), None,
                                            length=n_it)[0])

    res = {}
    for name, mk in (("kernel", kernel_loop), ("gather", gather_loop)):
        n1, n2 = iters // 4, iters
        f1, f2 = mk(n1), mk(n2)
        np.asarray(f1()); np.asarray(f2())
        t1 = min(_t(f1) for _ in range(3))
        t2 = min(_t(f2) for _ in range(3))
        res[name] = (t2 - t1) / (n2 - n1) / L * 1e6   # us per layer-call
    return res


def _t(f):
    t0 = time.perf_counter()
    np.asarray(f())
    return time.perf_counter() - t0


if __name__ == "__main__":
    for live, mb in ((256, 8), (256, 32), (512, 32), (1024, 32)):
        r = run(live, mb)
        print(json.dumps({
            "live": live, "max_blocks": mb, "block_size": BS,
            "kernel_us_per_layer": round(r["kernel"], 1),
            "gather_us_per_layer": round(r["gather"], 1),
            "speedup": round(r["gather"] / r["kernel"], 2)}))
