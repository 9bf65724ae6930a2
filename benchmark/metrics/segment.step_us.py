def read(ctx):
    sl = ctx.get("slice") or {}
    if not sl.get("steps") or not sl.get("device_s"):
        return None
    return 1e6 * sl["device_s"] / sl["steps"]
