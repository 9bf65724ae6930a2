def read(ctx):
    gap_ms = ctx["occupancy"].get("gap_starved_ms")
    if gap_ms is None or not ctx.get("window_s"):
        return None  # a program without gap counters: nothing to read
    return 100.0 * gap_ms / (1000.0 * ctx["window_s"])
