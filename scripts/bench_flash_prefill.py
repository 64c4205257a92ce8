#!/usr/bin/env python
"""Micro-bench: Pallas flash prefill kernel (causal DMA elision) vs the XLA
attention path on the real chip — the win-or-delete data.
Prints one JSON line per (seq, window)."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.ops import attention as attn_ops
from neuronx_distributed_inference_tpu.ops import flash_attention as fa

B, HQ, HKV, D = 1, 32, 8, 128


def run(s, window=0, iters=16):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, s, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, s, HKV, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, s, HKV, D)), jnp.bfloat16)
    scale = D ** -0.5
    pos = jnp.broadcast_to(jnp.arange(s), (B, s))
    mask = attn_ops.causal_mask(pos, pos, None, window, 0)

    def mk(fn, n):
        def loop():
            def body(acc, _):
                o = fn(q + acc * 1e-9)
                return acc + o.sum().astype(jnp.float32), None
            return jax.lax.scan(body, jnp.zeros(()), None, length=n)[0]
        return jax.jit(loop)

    def t(f):
        t0 = time.perf_counter()
        np.asarray(f())
        return time.perf_counter() - t0

    res = {}
    variants = {
        "kernel": lambda qq: fa.flash_attention(
            qq, k, v, scale=scale, causal=True, window=window),
        "xla": lambda qq: attn_ops.mha(qq, k, v, mask, scale),
    }
    for name, fn in variants.items():
        n1, n2 = iters // 4, iters
        f1, f2 = mk(fn, n1), mk(fn, n2)
        np.asarray(f1()); np.asarray(f2())
        t1 = min(t(f1) for _ in range(3))
        t2 = min(t(f2) for _ in range(3))
        res[name] = (t2 - t1) / (n2 - n1) * 1e3
    return res


if __name__ == "__main__":
    for s, w in ((1024, 0), (2048, 0), (4096, 0), (8192, 0), (4096, 1024)):
        r = run(s, w)
        print(json.dumps({
            "seq": s, "window": w,
            "kernel_ms": round(r["kernel"], 3),
            "xla_ms": round(r["xla"], 3),
            "speedup": round(r["xla"] / r["kernel"], 3)}))
