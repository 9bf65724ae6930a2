def read(ctx):
    occ = ctx["occupancy"]
    assigned = occ.get("lane_steps_assigned")
    if assigned is None or not occ.get("lane_steps"):
        return None  # a program without the run column, or no step
    parked = (assigned - occ["lane_steps_primary_run"]
              - occ["lane_steps_helper_run"])
    return 100.0 * parked / occ["lane_steps"]
