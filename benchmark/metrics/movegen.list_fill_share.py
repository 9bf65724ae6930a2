def read(ctx):
    occ = ctx["occupancy"]
    width = (ctx.get("config") or {}).get("max_moves")
    if not occ.get("movegen_nodes") or not occ.get("movegen_moves") or not width:
        return None  # a program without the movegen counters: nothing to read
    return 100.0 * occ["movegen_moves"] / (occ["movegen_nodes"] * width)
