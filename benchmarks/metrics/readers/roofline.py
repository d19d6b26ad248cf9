"""Roofline share of the searches answered in the traced interval: the
least time the chip could take for them (benchmarks/roofline.py) over the
device's busy seconds in that interval. ``kind`` says which work counts
(``bytes``-bound postings, or a slab read a ``batch``); the batches are the
rise of the ``batches`` series over the traced interval."""
from benchmarks import roofline
from benchmarks.metrics import counters


def read(ctx: dict, spec: dict):
    traced = ctx.get("traced")
    if traced is None:
        return None
    batches = 0.0
    if spec.get("batches"):
        batches = counters.delta(ctx["counters"]["traced"], spec["batches"])
    return roofline.share_pct(
        traced["works"], batches, traced["busy_s"], ctx["chips"],
        ctx["peaks"])
