def read(ctx):
    occ = ctx["occupancy"]
    total = occ.get("host_ms", 0.0) + occ.get("device_ms", 0.0)
    if total <= 0:
        return None
    return 100.0 * occ["host_ms"] / total
