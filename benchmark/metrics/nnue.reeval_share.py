def read(ctx):
    occ = ctx["occupancy"]
    chosen = (occ.get("eval_small") or 0) + (occ.get("eval_reeval") or 0)
    if not chosen:
        return None  # no eval counters, or the small net was never chosen
    return 100.0 * occ["eval_reeval"] / chosen
