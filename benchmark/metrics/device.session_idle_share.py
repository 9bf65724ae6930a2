from benchmark import trace_reduce


def read(ctx):
    in_session_s = trace_reduce.session_s(ctx["occupancy"])
    if ctx.get("busy_s") is None or in_session_s <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / in_session_s)
