"""Self time of the three scheduler stages of a pass, per dispatch."""

from harness import host_spans


def read(ctx):
    return host_spans.per_dispatch_ms(ctx, "sched_s")
