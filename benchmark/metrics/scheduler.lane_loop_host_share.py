PHASES = ("phase_lanes_ms", "phase_admit_ms", "phase_reap_ms", "phase_pv_ms")


def read(ctx):
    occ = ctx["occupancy"]
    total = occ.get("host_ms", 0.0) + occ.get("device_ms", 0.0)
    if any(occ.get(k) is None for k in PHASES) or total <= 0:
        return None  # a program without phase counters: nothing to read
    return 100.0 * sum(occ[k] for k in PHASES) / total
