"""Self time of ``dispatch.retire`` (its seconds less the ``fetch.tokens``
wait recorded under it), per dispatch; None where the program has no such
span."""

from harness import host_spans

SPAN = ("dispatch.retire",)


def read(ctx):
    delta = host_spans.host_seconds(ctx)
    n = host_spans.dispatches(ctx)
    if n <= 0 or not any(span in SPAN for span, _ in delta):
        return None
    return 1e3 * host_spans.self_seconds(delta, SPAN) / n
