"""A ``size: 0`` aggregation tree as one device program a segment.

The host half of ``ops/aggs.agg_tree``: :func:`plan` turns a request's
query and aggregation tree into the program's static spec and its int32
parameters over the segment's exact column codes (``index/segment.
column_code``), or declines; :func:`dispatch` enqueues the program, the
caller pulls its few-KB result once, and :func:`partials` keys it into
the partial the host collectors give, so the aggregators' own ``reduce``
merges it with partials of any other segment or shard.

In scope: a query that is a conjunction of ranges (``range``, ``term`` on
a numeric field, ``bool`` of ``filter``/``must`` of those,
``constant_score``, ``filtered``, ``match_all``) over columns with a
code, and an aggregation tree that is either one ``histogram`` or
fixed-interval ``date_histogram`` whose sub-aggregations are metrics, or
metrics alone (one bucket); metrics ``stats``, ``min``, ``max``, ``sum``,
``avg`` and ``value_count``, each on a field. Every other tree declines
to the host collectors
(``estpu_kernel_dispatch_total{kernel="agg_declined"}``), as do a column
without a code (floating kinds, millisecond dates whose span passes
``CODE_LIMIT``), an interval that is not a whole number of the column's
code units or whose denominator is not a power of two (the host path
divides doubles: floor(0.3 / 0.1) is 2), and more buckets than the
largest class.
"""
from __future__ import annotations

from fractions import Fraction
from math import ceil, floor
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from elasticsearch_tpu.index.segment import CODE_LIMIT
from elasticsearch_tpu.ops.aggs import (Metric, TreeSpec, bucket_class,
                                        int_sum_fits, last_block, unpack)

BUCKET_TYPES = {"histogram": frozenset({"field", "interval", "min_doc_count",
                                        "format"}),
                "date_histogram": frozenset({"field", "interval",
                                             "fixed_interval",
                                             "calendar_interval",
                                             "min_doc_count", "format"})}
# metric type -> which of (count, sum, min, max) its partial needs
METRIC_TYPES = {"stats": (True, True, True, True),
                "min": (False, False, True, False),
                "max": (False, False, False, True),
                "sum": (False, True, False, False),
                "avg": (True, True, False, False),
                "value_count": (True, False, False, False)}
_I32_MIN, _I32_MAX = -(2 ** 31), 2 ** 31 - 1


class Plan(NamedTuple):
    spec: TreeSpec
    params: np.ndarray  # int32[2F + 3]
    cols: tuple  # NumericColumn of each code column, in spec order
    bucket: Any  # the bucket aggregator, or None (one bucket)
    keys: Optional[List[float]]  # bucket key of each program bucket
    metric_aggs: list  # (aggregator, index into spec.metrics)


def tree_shape(aggs) -> Optional[Tuple[Any, list]]:
    """(bucket aggregator or None, metric aggregators) where the tree's
    shape is in scope, else None. Reads the request alone."""
    def metric_ok(a):
        return (type(a).__name__ in _METRIC_CLASSES and not a.subs
                and set(a.body) == {"field"})

    if len(aggs) == 1 and type(aggs[0]).__name__ in _BUCKET_CLASSES:
        b = aggs[0]
        if not set(b.body) <= BUCKET_TYPES[_BUCKET_CLASSES[type(b).__name__]]:
            return None
        if b.body.get("field") is None or not all(
                metric_ok(s) for s in b.subs):
            return None
        return b, list(b.subs)
    if aggs and all(metric_ok(a) for a in aggs):
        return None, list(aggs)
    return None


_BUCKET_CLASSES = {"HistogramAggregator": "histogram",
                   "DateHistogramAggregator": "date_histogram"}
_METRIC_CLASSES = {"StatsAggregator": "stats", "MinAggregator": "min",
                   "MaxAggregator": "max", "SumAggregator": "sum",
                   "AvgAggregator": "avg",
                   "ValueCountAggregator": "value_count"}


def _ranges(query) -> Optional[list]:
    """The query as a conjunction of ranges (RangeQuery, or ("term",
    TermQuery) for a term that is a point range), or None."""
    from elasticsearch_tpu.search import queries as Q

    if isinstance(query, Q.MatchAllQuery):
        return []
    if isinstance(query, Q.RangeQuery):
        return [query]
    if isinstance(query, Q.TermQuery):
        return [("term", query)]
    if isinstance(query, Q.ConstantScoreQuery):
        return _ranges(query.inner)
    if isinstance(query, Q.BoolQuery):
        if query.should or query.must_not or not (query.must or query.filter):
            return None
        out = []
        for q in (*query.must, *query.filter):
            r = _ranges(q)
            if r is None:
                return None
            out.extend(r)
        return out
    return None


def _fraction(v) -> Optional[Fraction]:
    if isinstance(v, bool):
        return None
    try:
        return Fraction(v) if isinstance(v, (int, float)) else Fraction(
            str(v).strip())
    except (TypeError, ValueError, ZeroDivisionError):
        return None


def _code_bounds(col, lo, ilo, hi, ihi) -> Optional[Tuple[int, int]]:
    """Inclusive code bounds of lo <(=) value <(=) hi on a coded column
    (value = (base + code * step) / factor), exactly, or None."""
    base, step, factor = col.code_base, col.code_step, col.code_factor
    c_lo, c_hi = _I32_MIN + 1, _I32_MAX
    if lo is not None:
        f = _fraction(lo)
        if f is None:
            return None
        x = (f * factor - base) / step
        c_lo = max(c_lo, ceil(x) if ilo else floor(x) + 1)
    if hi is not None:
        f = _fraction(hi)
        if f is None:
            return None
        x = (f * factor - base) / step
        c_hi = min(c_hi, floor(x) if ihi else ceil(x) - 1)
    c_lo = min(max(c_lo, _I32_MIN + 1), _I32_MAX)
    c_hi = max(min(c_hi, _I32_MAX), _I32_MIN + 1)
    return c_lo, c_hi


def _interval(bucket) -> Optional[Fraction]:
    """The bucket width in value units, or None (calendar interval)."""
    if _BUCKET_CLASSES[type(bucket).__name__] == "date_histogram":
        if bucket._cal_months() is not None:
            return None
        return Fraction(int(bucket._interval()))
    # as written (0.1 is 1/10, not the double nearest it)
    f = _fraction(str(bucket.body.get("interval")))
    # a width with a power-of-two denominator divides the column's double
    # values exactly as the host path (and ES) does; 0.1 would not
    if f is None or f <= 0 or f.denominator & (f.denominator - 1):
        return None
    return f


def plan(ctx, query, aggs) -> Optional[Plan]:
    """The program for this segment, or None (the caller declines)."""
    shape = tree_shape(aggs)
    if shape is None:
        return None
    bucket, metric_aggs = shape
    conj = _ranges(query)
    if conj is None:
        return None
    cols: List[Any] = []
    col_at: Dict[str, int] = {}

    def coded(field) -> Optional[int]:
        if field not in col_at:
            col = ctx.col(field)
            if col is None or not col.has_code:
                return None
            col_at[field] = len(cols)
            cols.append(col)
        return col_at[field]

    filters, params = [], []
    span: Dict[int, Tuple[int, int]] = {}
    for r in conj:
        if isinstance(r, tuple):  # a term on a numeric field: [v, v]
            q = r[1]
            fm = ctx.mappings.get(q.field)
            if fm is None or not fm.is_numeric:
                return None
            field, lo, ilo, hi, ihi = q.field, q.value, True, q.value, True
        else:
            field = r.field
            lo, ilo, hi, ihi = r._bounds(ctx)
        c = coded(field)
        if c is None:
            return None
        b = _code_bounds(cols[c], lo, ilo, hi, ihi)
        if b is None:
            return None
        filters.append(c)
        params.extend(b)
        old = span.get(c, (_I32_MIN, _I32_MAX))
        span[c] = (max(old[0], b[0]), min(old[1], b[1]))

    key_col, keys, B = -1, None, 1
    c0 = q = 1
    if bucket is not None:
        k = coded(bucket.body["field"])
        iv = _interval(bucket)
        if k is None or iv is None:
            return None
        col = cols[k]
        qf = iv * col.code_factor / col.code_step
        if qf.denominator != 1 or col.code_base % col.code_step:
            return None
        q = int(qf)
        bias = col.code_base // col.code_step
        lo_c, hi_c = span.get(k, (_I32_MIN, _I32_MAX))
        lo_c, hi_c = max(lo_c, col.code_min), min(hi_c, col.code_max)
        if lo_c > hi_c:  # nothing can match: one empty bucket
            kmin = kmax = 0
            c0 = 0
        else:
            kmin, kmax = (lo_c + bias) // q, (hi_c + bias) // q
            c0 = kmin * q - bias
        B = bucket_class(kmax - kmin + 1)
        if B is None or abs(c0) >= CODE_LIMIT or q >= CODE_LIMIT:
            return None
        key_col = k
        width = float(iv)
        keys = [float(kmin + j) * width for j in range(B)]
    else:
        B = bucket_class(1)

    need: Dict[int, List[bool]] = {}
    owner = []
    for a in metric_aggs:
        c = coded(a.body["field"])
        if c is None:
            return None
        wants = METRIC_TYPES[_METRIC_CLASSES[type(a).__name__]]
        cur = need.setdefault(c, [False] * 4)
        need[c] = [x or y for x, y in zip(cur, wants)]
        owner.append((a, c))
    order = sorted(need)
    metrics = []
    for c in order:
        cnt, s, mn, mx = need[c]
        # a column with a value on every document counts as the bucket
        full = cols[c].value_count >= ctx.segment.num_docs
        # exact int32 partials where the column's codes cannot overflow
        # one between two drains (epoch seconds over a year can)
        exact = s and int_sum_fits(cols[c].code_min, cols[c].code_max)
        metrics.append(Metric(c, cnt and not full, s, mn, mx, exact))
    spec = TreeSpec(n_cols=len(cols), filters=tuple(filters),
                    key_col=key_col, B=B, metrics=tuple(metrics))
    # the kernel's grid stops at the block of the last used slot: maxDoc
    # (num_docs), deleted documents included, never the live count
    last = last_block(ctx.D, ctx.segment.num_docs)
    params = np.asarray(params + [c0, q, last], np.int32)
    return Plan(spec, params, tuple(cols), bucket, keys,
                [(a, order.index(c)) for a, c in owner])


def in_scope(query, aggs) -> bool:
    """True when the request's shape is the program's: the mesh path then
    leaves it to the host loop, which plans each segment once (a segment
    the plan declines runs the host collectors there)."""
    return tree_shape(aggs) is not None and _ranges(query) is not None


def dispatch(ctx, p: Plan):
    """Enqueue the program; the device int32 vector (ops/aggs.unpack)."""
    import jax

    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.ops.aggs import agg_tree, block_slots, use_kernel

    seg = ctx.segment
    kernel = use_kernel(ctx.D)
    # slots the program scans times the bucket passes it makes over them
    slots = (int(p.params[-1]) + 1) * block_slots(ctx.D) if kernel else ctx.D
    kernels.record("agg_bucket_slots", slots * p.spec.B)
    if kernel:  # the metric sums it adds up as exact int32 partials
        kernels.record("agg_int_sums",
                       sum(m.int_sum for m in p.spec.metrics))
    # a few int32 scalars a search, uploaded with the call  # tpulint: offbudget
    params = jax.device_put(p.params, seg.device) if seg.device is not None \
        else p.params
    # spec.B is a BUCKET_CLASSES class, the columns D-long: the program's
    # own shape classes  # tpulint: bucketed
    return agg_tree(params, seg.live_i8, *(c.code for c in p.cols),
                    spec=p.spec, kernel=kernel)


def _value(col, code) -> float:
    return (col.code_base + int(code) * col.code_step) / col.code_factor


def partials(ctx, p: Plan, words) -> Tuple[int, Dict[str, Any]]:
    """(matching documents, {agg name: partial}) from the pulled vector,
    in the host collectors' partial forms."""
    total, counts, mets = unpack(p.spec, words)
    full_n = counts

    def metric_partial(agg, j, b):
        m = p.spec.metrics[j]
        col = p.cols[m.col]
        got = mets[j]
        n = int(got["count"][b]) if "count" in got else int(full_n[b])
        kind = _METRIC_CLASSES[type(agg).__name__]
        s = ((n * col.code_base + col.code_step * float(got["sum"][b]))
             / col.code_factor) if "sum" in got else 0.0
        mn = _value(col, got["min"][b]) if n and "min" in got else None
        mx = _value(col, got["max"][b]) if n and "max" in got else None
        if kind == "stats":
            return {"count": n, "sum": s, "min": mn, "max": mx}
        if kind == "avg":
            return (s, n)
        if kind == "sum":
            return s
        if kind == "value_count":
            return n
        return mn if kind == "min" else mx

    if p.bucket is None:
        return total, {a.name: metric_partial(a, j, 0)
                       for a, j in p.metric_aggs}
    buckets = {}
    for b in np.nonzero(counts)[0].tolist():
        entry = {"doc_count": int(counts[b])}
        if p.bucket.subs:
            entry["subs"] = {a.name: metric_partial(a, j, b)
                             for a, j in p.metric_aggs}
        buckets[p.keys[b]] = entry
    return total, {p.bucket.name: {"buckets": buckets}}
