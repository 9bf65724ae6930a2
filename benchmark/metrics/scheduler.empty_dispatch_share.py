def read(ctx):
    occ = ctx["occupancy"]
    empty = occ.get("segments_empty")
    if empty is None or not occ.get("segments", 0) + empty:
        return None  # a program that does not count them, or no dispatch
    return 100.0 * empty / (occ["segments"] + empty)
