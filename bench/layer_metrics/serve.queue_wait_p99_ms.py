"""Serving layer: the 99th percentile of the wait from a search's due
time to the dispatch of the batch that carries it (host clock)."""
import numpy as np


def read(ctx):
    w = ctx.queue_wait_s
    return float(np.percentile(w, 99) * 1e3) if len(w) else None
