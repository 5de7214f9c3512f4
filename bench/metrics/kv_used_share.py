"""Share of the KV rows reserved for the decoding sequences that they
attend, in %: the program's ``decode_window_kv_rows`` over its
``decode_window_kv_reserved_rows`` (the pages granted at admission, for
prompt and every output token), deltas over the window."""


def read(ctx):
    reserved = ctx.counters.get("decode_window_kv_reserved_rows", 0)
    if not reserved:
        return None
    return 100.0 * ctx.counters["decode_window_kv_rows"] / reserved
