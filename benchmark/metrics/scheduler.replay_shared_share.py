def read(ctx):
    occ = ctx["occupancy"]
    parsed = occ.get("submit_moves_parsed")
    if parsed is None or not occ.get("submit_prefix_moves"):
        return None  # a program without the walk's counters, or no moves
    return 100.0 * (1.0 - parsed / occ["submit_prefix_moves"])
