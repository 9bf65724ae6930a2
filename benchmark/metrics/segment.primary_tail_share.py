def read(ctx):
    occ = ctx["occupancy"]
    tail = occ.get("steps_after_last_primary")
    if tail is None or not occ.get("steps"):
        return None  # a program without the run column, or no step
    return 100.0 * tail / occ["steps"]
