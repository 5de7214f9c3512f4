"""Device time per execution of the compiled chunked-prefill step
(``_chunk_step``) on the first chip, from the trace, in ms."""
from bench import tracing


def read(ctx):
    t, n = tracing.program_time(ctx.device0, r"_chunk_step", ctx.lo, ctx.hi)
    return t / n * 1e3 if n else None
