"""Host time of an engine step, in ms: the mean over the window's
``serve.step`` spans of their length less the part their
``serve.decode.sync`` children cover, the wait for the sampled ids. What is
left is the engine's own work: admission, prefill and decode dispatch,
emitting tokens and the step's accounting."""
from bench import spans


def read(ctx):
    host = spans.of_run(ctx)
    steps = spans.named(host, "serve.step", ctx.lo, ctx.hi)
    if not steps:
        return None
    syncs = spans.inside(spans.named(host, "serve.decode.sync", ctx.lo,
                                     ctx.hi), steps)
    own = sum(d - sum(c[2] for c in held)
              for (_, _, d), held in zip(steps, syncs))
    return own / len(steps) * 1e-6
