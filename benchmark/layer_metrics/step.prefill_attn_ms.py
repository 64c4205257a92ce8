"""Device self time under scope ``attn`` per execution of the prefill
step program."""

from harness import host_spans


def read(ctx):
    return host_spans.program_scope_ms(ctx, "paged", 'widest', "attn")
