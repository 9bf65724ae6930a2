def read(ctx):
    occ = ctx["occupancy"]
    total = occ.get("host_ms", 0.0) + occ.get("device_ms", 0.0)
    if occ.get("phase_refill_ms") is None or total <= 0:
        return None  # a program without phase counters: nothing to read
    return 100.0 * occ["phase_refill_ms"] / total
