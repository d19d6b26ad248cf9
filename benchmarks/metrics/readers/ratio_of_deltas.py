"""``scale`` x (rise of ``num`` over the interval) / (rise of ``den``).
``interval`` is ``window`` (the whole measured window) or ``traced``.
Nothing to read where the denominator did not move."""
from benchmarks.metrics import counters


def read(ctx: dict, spec: dict):
    pair = ctx["counters"].get(spec.get("interval", "window"))
    if pair is None:
        return None
    den = counters.delta(pair, spec["den"])
    if not den > 0:
        return None
    return float(spec.get("scale", 1.0)) * counters.delta(
        pair, spec["num"]) / den
