from benchmark import trace_reduce


def read(ctx):
    busy_s = trace_reduce.window_busy_s(ctx["occupancy"], ctx.get("trace"))
    if busy_s is None or not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - busy_s / ctx["window_s"])
