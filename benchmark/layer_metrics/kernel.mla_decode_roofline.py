"""Roofline share of the latent (MLA) paged decode attention kernel.

The trace names the Mosaic call apart: its operations on the ``XLA Ops`` line
are ``mla_decode_attention[.n]`` (one call per attention sub-block inside the
width-1 step program). Live tokens and live rows are the mean of their values
at the two edges of the profiled slice (~3 s of steady load).

The yardstick is computed here from the configuration's published keys, not
from what the kernel reads or multiplies: one call must, at the least, read
every live token's latent row once - ``kv_lora_rank + qk_rope_head_dim``
values, whatever lane padding the pool stores them with - and read a folded
query and write a latent output for every head of every row; and it must
multiply every head's query with every live row (``rank + rope`` lanes) and
its probabilities with the row's ``rank`` value lanes. The least time is the
larger of bytes over the chip's HBM bandwidth and operations over its bf16
peak (``peaks.json``): at 64 heads the two are 121 FLOP a byte apart from
equal, so the bytes bind on a v5e. A padded lane, a page past a row's end, the
block table and the W_UK / W_UV folds outside the kernel are the program's
overhead, not the algorithm's need."""

from harness.kernel_bytes import DTYPE_BYTES

KERNEL = "mla_decode_attention"


def mla_decode_need(cfg, live_tokens: float, rows: float):
    """``(bytes, flops)`` ONE call (one attention sub-block, one chip) needs
    for a step over ``live_tokens`` cached tokens in ``rows`` rows."""
    heads = cfg["num_attention_heads"] // cfg["tp"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    size = DTYPE_BYTES[cfg["dtype"]]
    need_bytes = (live_tokens * (rank + rope)
                  + rows * heads * ((rank + rope) + rank)) * size
    need_flops = live_tokens * heads * 2 * ((rank + rope) + rank)
    return need_bytes, need_flops


def read(ctx):
    cfg = ctx["config"]
    trace, edges = ctx.get("trace"), ctx["slice"]
    if "kv_lora_rank" not in cfg or not trace \
            or not edges.get("before") or not edges.get("after"):
        return None
    ops = trace.get("ops_by_program", {}).get("paged.w1", {})
    calls = [v for name, v in ops.items() if name.split(".")[0] == KERNEL]
    seconds = sum(v["seconds"] for v in calls)
    count = sum(v["count"] for v in calls)
    if not count or seconds <= 0:
        return None

    def mean_of(key):
        return (edges["before"]["counters"][key]
                + edges["after"]["counters"][key]) / 2.0
    need_bytes, need_flops = mla_decode_need(
        cfg, mean_of("kv.live_tokens"), mean_of("kv.live_rows"))
    least_s = max(need_bytes / (ctx["peaks"]["hbm_gbps"] * 1e9),
                  need_flops / (ctx["peaks"]["bf16_tflops"] * 1e12))
    return 100.0 * least_s / (seconds / count)
