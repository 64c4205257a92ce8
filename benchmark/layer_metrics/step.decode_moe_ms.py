"""Device self time under scope ``moe`` per execution of the decode
step program."""

from harness import host_spans


def read(ctx):
    return host_spans.program_scope_ms(ctx, "paged", 1, "moe")
