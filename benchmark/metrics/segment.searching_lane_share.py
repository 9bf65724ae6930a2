def read(ctx):
    occ = ctx["occupancy"]
    runs = [occ.get(f"lane_steps_{role}_run")
            for role in ("primary", "helper", "stray", "free")]
    if None in runs or not occ.get("lane_steps"):
        return None  # a program without the run column, or no step
    return 100.0 * sum(runs) / occ["lane_steps"]
