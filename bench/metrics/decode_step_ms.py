"""Device time per execution of the compiled decode step (``_decode_all``)
on the first chip, from the trace's program events, in ms."""
from bench import tracing


def read(ctx):
    t, n = tracing.program_time(ctx.device0, r"_decode_all", ctx.lo, ctx.hi)
    return t / n * 1e3 if n else None
