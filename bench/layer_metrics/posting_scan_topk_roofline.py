"""Kernel layer: ``posting_scan_topk``'s share of its roofline over the
traced search batches (bench/harness/roofline.py says what is counted)."""
from harness import roofline


def read(ctx):
    t = ctx.trace.op_s.get("posting_scan_topk") if ctx.trace else None
    if not t or not ctx.probes:
        return None
    # the i-th traced kernel call scanned the i-th traced batch's probes
    probes = ctx.probes[:ctx.trace.op_n["posting_scan_topk"]]
    ix = ctx.config["index"]
    nbytes, ops = roofline.posting_scan_work(probes,
                                             capacity=ix["capacity"],
                                             dim=ix["dim"])
    return roofline.share(nbytes, ops, t, ctx.peak)[0]
