"""Driver layer: host milliseconds spent in insert and delete calls per
1,000 acknowledged updates in the window."""


def read(ctx):
    t = sum(b - a for n, a, b in ctx.spans if n in ("insert", "delete"))
    return t * 1e3 / (ctx.acked_updates / 1e3) if ctx.acked_updates else None
