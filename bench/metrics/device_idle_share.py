"""Share of the traced window in which no operation ran on the first chip,
in %: one minus the union of its op intervals over the window."""
from bench import tracing


def read(ctx):
    busy = tracing.busy_ns(ctx.device0, ctx.lo, ctx.hi)
    if not busy:
        return None
    return 100.0 * (1.0 - busy / (ctx.hi - ctx.lo))
