def read(ctx):
    if ctx.get("busy_s") is None or not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
