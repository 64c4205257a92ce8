"""Pad share of prefill dispatches. ``prefill_padded_tokens`` is rows x width
of every chunk dispatch — the real tokens are inside it — so the share of
slots wasted is ``(padded - real) / padded`` (ISSUE 24 wrote it as
``padded / (padded + real)``, which reads the counter as pad-only)."""


def read(ctx):
    def delta(key):
        return (ctx["after"]["counters"].get(key, 0)
                - ctx["before"]["counters"].get(key, 0))
    padded = delta("host_stats.prefill_padded_tokens")
    real = delta("host_stats.prefill_real_tokens")
    return None if padded <= 0 else 100.0 * (padded - real) / padded
