"""``scale`` x (rise of ``series`` over the window) / searches answered in
it: what one search costs of a quantity the program only totals."""
from benchmarks.metrics import counters


def read(ctx: dict, spec: dict):
    answered = ctx["observed"].get("answered")
    if not answered:
        return None
    return float(spec.get("scale", 1.0)) * counters.delta(
        ctx["counters"]["window"], spec["series"]) / answered
