"""Device idle time while the host was in the ``dispatch`` class of spans."""

from harness import host_spans


def read(ctx):
    return host_spans.idle_share(ctx, "dispatch")
