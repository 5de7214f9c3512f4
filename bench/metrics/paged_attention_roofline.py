"""Paged decode attention's share of its roofline, in %: the least time
for the FLOPs and the K/V bytes the decode steps needed (every context row
of every stepped sequence, read once in every layer) over the summed device
time of the paged-attention Pallas kernel (``_pa_kernel``, bf16 pools) on
the first chip."""
from bench import costs, tracing


def read(ctx):
    t, n = tracing.op_time(ctx.device0, "_pa_kernel",
                           ctx.lo, ctx.hi)
    rows = ctx.counters.get("decode_window_kv_rows", 0)
    if not n or not rows:
        return None
    flops, nbytes = ctx.shape.paged_attention(rows)
    least = ctx.shape.n_layers * costs.least_time(flops, nbytes, ctx.peaks)
    return 100.0 * least / t
