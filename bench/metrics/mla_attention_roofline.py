"""Absorbed latent-attention decode's share of its roofline, in %: the
least time for the FLOPs and the latent-row bytes the decode steps needed
(every context row of every stepped sequence, ``decode_window_kv_rows``,
read once for all heads in every layer; the architecture's
``mla_attention``) over the summed device time of the latent-attention
Pallas kernel (``_mla_kernel``) on the first chip. Nothing to read where the
architecture has no latent attention or the kernel did not run."""
from bench import costs, tracing


def read(ctx):
    mla = getattr(ctx.shape, "mla_attention", None)
    rows = ctx.counters.get("decode_window_kv_rows", 0)
    if mla is None or not rows:
        return None
    t, n = tracing.op_time(ctx.device0, "_mla_kernel", ctx.lo, ctx.hi)
    if not n:
        return None
    flops, nbytes = mla(rows)
    least = ctx.shape.n_layers * costs.least_time(flops, nbytes, ctx.peaks)
    return 100.0 * least / t
