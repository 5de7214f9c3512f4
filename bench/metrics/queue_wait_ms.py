"""Mean time a request waited in the scheduler's queue before admission,
in ms: the program's ``queue_wait_s`` (summed from each request's latest
submit or requeue to its placement in a slot) over its ``prefills`` (one
per placement), deltas over the window."""


def read(ctx):
    n = ctx.counters.get("prefills", 0)
    if "queue_wait_s" not in ctx.counters or not n:
        return None
    return ctx.counters["queue_wait_s"] / n * 1e3
