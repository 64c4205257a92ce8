#!/usr/bin/env python
"""Measure the XLA decode-MoE path against the HBM roofline on the real
chip (reference analog: the moe_token_gen NKI kernel of
SURVEY §2.10 — this measurement decides whether a Pallas token-gen MoE
kernel is warranted).

Decode MoE at small batch runs the all-experts dense path: every step
streams ALL expert weights once, so roofline = expert_bytes / HBM_BW.
Prints one JSON line with ms/step and the fraction of roofline."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_inference_tpu.modules.moe import MoESpec, moe_block

B, H, E, I = 4, 2048, 8, 4096          # mixtral-shaped slice, bf16
moe = MoESpec(num_experts=E, top_k=2, intermediate_size=I)
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 5)
lw = {
    "router": jax.random.normal(ks[0], (H, E), jnp.float32) * 0.02,
    "expert_gate": jax.random.normal(ks[1], (E, H, I), jnp.bfloat16) * 0.02,
    "expert_up": jax.random.normal(ks[2], (E, H, I), jnp.bfloat16) * 0.02,
    "expert_down": jax.random.normal(ks[3], (E, I, H), jnp.bfloat16) * 0.02,
}
x = jax.random.normal(ks[4], (B, 1, H), jnp.bfloat16)


def make_loop(n):
    def loop(lw, x):
        def body(h, _):
            y = moe_block(moe, h, lw, phase="decode")
            return (h + y * 1e-3).astype(h.dtype), None
        h, _ = jax.lax.scan(body, x, None, length=n)
        return h.sum().astype(jnp.float32)
    return jax.jit(loop)


def t(fn):
    reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(fn(lw, x))
        reps.append(time.perf_counter() - t0)
    return min(reps)


N1, N2 = 8, 40
f1, f2 = make_loop(N1), make_loop(N2)
np.asarray(f1(lw, x)); np.asarray(f2(lw, x))        # compile
per_step = (t(f2) - t(f1)) / (N2 - N1)

expert_bytes = sum(int(np.prod(w.shape)) * 2 for k, w in lw.items()
                   if k.startswith("expert"))
hbm = float(os.environ.get("NXDI_TPU_HBM_GBPS", "819")) * 1e9
roofline_s = expert_bytes / hbm
print(json.dumps({
    "metric": "moe_decode_ms_per_step",
    "value": round(per_step * 1e3, 4),
    "unit": "ms",
    "vs_baseline": round(roofline_s / per_step, 4),
    "details": {"roofline_ms": round(roofline_s * 1e3, 4),
                "expert_mbytes": expert_bytes // 2**20,
                "geometry": f"B{B} H{H} E{E} I{I} top2 bf16",
                "verdict": ("XLA path within 15% of roofline — no Pallas "
                            "tokengen kernel needed"
                            if roofline_s / per_step >= 0.85 else
                            "XLA path >15% off roofline — a Pallas tokengen "
                            "MoE kernel is warranted")},
}))
