def read(ctx):
    occ = ctx["occupancy"]
    queue_ms = occ.get("position_queue_ms")
    if queue_ms is None or not occ.get("position_engine_ms"):
        return None  # a program without the position clocks, or none finalized
    return 100.0 * queue_ms / occ["position_engine_ms"]
