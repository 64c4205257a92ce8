"""Host seconds inside ``loop.yield``, per dispatch; None where the program has
no such counter (a data-only ``counter_ratio`` would read 0 there)."""

from harness import host_spans


def read(ctx):
    return host_spans.per_dispatch_ms(ctx, "loop_yield_s")
