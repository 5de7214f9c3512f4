"""The whole step's share of the chips' bf16 peak, in %: the model FLOPs of
every token the window processed over the window's length.

Decode: every sequence the program stepped (``decode_window_batch``) pays
its matmuls and logits, and attends over ``decode_window_kv_rows`` context
rows. Prefill: each request whose first token came inside the window pays
for its prompt from the first position the prefix cache did not hold (its
system prompt, where it has one), with attention over the growing context.
"""


def read(ctx):
    s = ctx.shape
    batch = ctx.counters.get("decode_window_batch", 0)
    rows = ctx.counters.get("decode_window_kv_rows", 0)
    flops = batch * s.token_flops(0, logits=True) + s.context_flops(rows)
    t0, t1 = ctx.extra["t0"], ctx.extra["t1"]
    sys_len = ctx.extra.get("prefix_tokens", 0)
    for r in ctx.requests:
        ts = r["tokens_ts"]
        if ts and t0 <= ts[0] <= t1:
            start = sys_len if r["prefix"] >= 0 else 0
            flops += s.prefill_flops(start, r["prompt"])
    if not flops:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks.bf16_flops)
