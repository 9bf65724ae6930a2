def read(ctx):
    occ = ctx["occupancy"]
    in_session = occ.get("host_ms", 0.0) + occ.get("device_ms", 0.0)
    if in_session <= 0 or not ctx.get("window_s"):
        return None
    return 100.0 * in_session / (1000.0 * ctx["window_s"])
