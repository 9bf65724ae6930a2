def read(ctx):
    return (ctx.get("trace") or {}).get("idle_share")
