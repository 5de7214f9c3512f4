"""The ``gemm`` Pallas kernel's (``_gemm_kernel``) share of its roofline,
in %. It runs the MLP's gate, up and down projections of every layer: over
``max_slots`` rows in each decode step and ``prefill_chunk`` rows in each
prefill chunk. The least time of each matmul (operands read once, result
written once) is summed over the steps the trace saw and divided by the
kernel's summed device time on the first chip."""
from bench import costs, tracing


def _least(ctx, m: int) -> float:
    s = ctx.shape
    d, f = s.d_model, s.d_ff
    return s.n_layers * (
        2 * costs.least_time(*s.gemm(m, d, f), ctx.peaks)
        + costs.least_time(*s.gemm(m, f, d), ctx.peaks))


def read(ctx):
    t, n = tracing.op_time(ctx.device0, "_gemm_kernel", ctx.lo, ctx.hi)
    if not n:
        return None
    _, n_dec = tracing.program_time(ctx.device0, r"_decode_all",
                                    ctx.lo, ctx.hi)
    _, n_pre = tracing.program_time(ctx.device0, r"_chunk_step",
                                    ctx.lo, ctx.hi)
    least = (n_dec * _least(ctx, ctx.engine["max_slots"])
             + n_pre * _least(ctx, ctx.engine["chunk"]))
    return 100.0 * least / t
