def read(ctx):
    occ = ctx["occupancy"]
    if not occ.get("acc_rows") or not occ.get("movegen_nodes"):
        return None  # no counters, or a program that gathers no row
    return occ["acc_rows"] / occ["movegen_nodes"]
