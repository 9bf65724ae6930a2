def read(ctx):
    occ = ctx["occupancy"]
    counts = [occ.get(name) or 0 for name in ("eval_small", "eval_reeval", "eval_big")]
    if not sum(counts):
        return None  # a program without the eval counters, or no expansion
    return 100.0 * counts[0] / sum(counts)
