def read(ctx):
    occ = ctx["occupancy"]
    if occ.get("submit_ms") is None or not occ.get("positions_submitted"):
        return None  # a program without submit counters, or nothing submitted
    return occ["submit_ms"] / occ["positions_submitted"]
