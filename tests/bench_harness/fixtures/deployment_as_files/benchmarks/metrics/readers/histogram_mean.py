"""``scale`` x the mean of a Prometheus histogram over the window: the rise
of ``<family>_sum`` over the rise of ``<family>_count``, on the rows that
carry ``labels``. Nothing to read where nothing was observed."""
from benchmarks.metrics import counters


def read(ctx: dict, spec: dict):
    def rise(suffix):
        return counters.delta(ctx["counters"]["window"], [
            {"family": spec["family"] + suffix,
             "labels": spec.get("labels")}])

    n = rise("_count")
    if not n > 0:
        return None
    return float(spec.get("scale", 1.0)) * rise("_sum") / n
