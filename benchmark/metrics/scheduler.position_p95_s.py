def read(ctx):
    return (ctx.get("latency") or {}).get("p95_s")
