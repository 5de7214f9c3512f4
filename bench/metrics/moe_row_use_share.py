"""Share of the expert layers' matmul rows that carried a routed token, in
%: the program's ``moe_rows_routed`` (tokens x experts per token x expert
layers, over the decode and chunked-prefill steps) over its
``moe_rows_computed`` (the rows those steps' expert matmuls ran, padding
and idle slots included), deltas over the window. Nothing to read where
the program counts no expert rows."""


def read(ctx):
    computed = ctx.counters.get("moe_rows_computed", 0)
    if not computed:
        return None
    return 100.0 * ctx.counters.get("moe_rows_routed", 0) / computed
