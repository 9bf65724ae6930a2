def read(ctx):
    if not ctx.get("nodes") or not ctx.get("window_s"):
        return None
    return ctx["nodes"] / ctx["window_s"]
