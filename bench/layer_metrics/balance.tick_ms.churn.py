"""Background layer: mean host time of one background tick (execute
the marked ops, drain the cache, mark, GC, PQ retrain on cadence)."""


def read(ctx):
    t = [b - a for n, a, b in ctx.spans if n == "tick"]
    return sum(t) / len(t) * 1e3 if t else None
