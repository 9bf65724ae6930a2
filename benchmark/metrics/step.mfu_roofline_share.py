from benchmark import work_count


def read(ctx):
    if ctx.get("busy_s") is None or not ctx.get("nodes"):
        return None
    share, bound = work_count.roofline_share(
        ctx["nodes"], ctx["busy_s"], ctx["per_node"], ctx["peak"])
    ctx.setdefault("notes", {})["step.mfu_roofline_share.bound"] = bound
    return share
