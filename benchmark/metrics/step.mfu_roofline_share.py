from benchmark import trace_reduce, work_count


def read(ctx):
    busy_s = trace_reduce.window_busy_s(ctx["occupancy"], ctx.get("trace"))
    if busy_s is None or not ctx.get("nodes"):
        return None
    share, bound = work_count.roofline_share(
        ctx["nodes"], busy_s, ctx["per_node"], ctx["peak"])
    ctx.setdefault("notes", {})["step.mfu_roofline_share.bound"] = bound
    return share
