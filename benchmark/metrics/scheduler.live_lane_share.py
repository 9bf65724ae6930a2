def read(ctx):
    occ = ctx["occupancy"]
    if not occ.get("lane_steps"):
        return None
    return 100.0 * occ["live_lane_steps"] / occ["lane_steps"]
