def read(ctx):
    occ = ctx["occupancy"]
    if not occ.get("movegen_moves") or not occ.get("movegen_drops"):
        return None  # no counters, or a program that generates no drop
    return 100.0 * occ["movegen_drops"] / occ["movegen_moves"]
