"""Background layer: device time of the background-round program
(``jit_background_round``) per tick whose span the trace holds."""


def read(ctx):
    ticks = ctx.trace.spans.get("tick") if ctx.trace else None
    s = ctx.trace.module_s.get("jit_background_round") if ticks else None
    return None if s is None else s * 1e3 / ticks
