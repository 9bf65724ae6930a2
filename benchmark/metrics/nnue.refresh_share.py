def read(ctx):
    occ = ctx["occupancy"]
    made = (occ.get("acc_updates") or 0) + (occ.get("acc_refreshes") or 0)
    if not made:
        return None  # a program without the accumulator counters
    return 100.0 * occ["acc_refreshes"] / made
