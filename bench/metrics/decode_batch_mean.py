"""Mean sequences per decode step over the window (engine host loop):
the program's ``decode_window_batch`` over its ``decode_steps``."""


def read(ctx):
    steps = ctx.counters.get("decode_steps", 0)
    if not steps:
        return None
    return ctx.counters["decode_window_batch"] / steps
