"""Device idle time inside the engine's wait for the sampled ids, in ms per
wait: the first chip's idle time inside the window's ``serve.decode.sync``
spans over their count. Near zero while the device computes the step the
host waits for; a stalled device-to-host copy shows here."""
from bench import spans


def read(ctx):
    syncs = spans.named(spans.of_run(ctx), "serve.decode.sync",
                        ctx.lo, ctx.hi)
    if not syncs:
        return None
    idle = spans.idle_inside(ctx.device0, syncs, ctx.lo, ctx.hi)
    return idle / len(syncs) * 1e-6
