def read(ctx):
    if not ctx.get("peak_bytes"):
        return None
    return 100.0 * ctx["peak_bytes"] / ctx["peak"]["hbm_bytes"]
