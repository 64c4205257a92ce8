"""Self time of the dispatch stage and of the prefill-chunk dispatches, per
dispatch; also prints where the loop thread's whole window went."""

from harness import host_spans


def read(ctx):
    host_spans.say_host_breakdown(ctx)
    return host_spans.per_dispatch_ms(ctx, "dispatch_self_s")
