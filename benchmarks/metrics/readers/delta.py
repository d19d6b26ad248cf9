"""Rise of the ``series`` over the interval (a count; 0 is a reading)."""
from benchmarks.metrics import counters


def read(ctx: dict, spec: dict):
    pair = ctx["counters"].get(spec.get("interval", "window"))
    if pair is None:
        return None
    return counters.delta(pair, spec["series"])
