"""Driver layer: mean host time per search batch, from the dispatch
call to the return of its collect (the engine's overlapped update flush
and tick, when one falls in between, included)."""


def read(ctx):
    b = ctx.batch_s
    return float(b.mean() * 1e3) if len(b) else None
