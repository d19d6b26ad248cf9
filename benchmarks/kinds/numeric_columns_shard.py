"""``numeric_columns_shard``: one frozen segment of numeric and date doc
values, a pool of ``size: 0`` aggregation searches, the float64 reference
of ``reference/aggs.py``.

The columns are built from the generator's arrays by the product's own
columnar codec (``index.segment.numeric_column``), and the segment holds
its ids, sources and stored fields without a Python object a document
(``RangeIds``, ``Uniform``): the only way to 165M documents inside a run.
A program without that codec fails here, at load. An answer is the reply's
``aggregations``; it is held to the reference bucket by bucket. The
control is the reference with every ``total_amount`` rounded to bfloat16.
"""
from __future__ import annotations

import numpy as np

from benchmarks.data import taxis
from benchmarks.loaders import Loaded, _sized
from benchmarks.reference.aggs import AggReference

DIST_AGG, AMOUNT_STATS, DATE_AGG = ("distance_histo", "total_amount_stats",
                                    "dropoffs_over_time")


def _ddmmyyyy(day: int) -> str:
    d = np.datetime64("2015-01-01") + np.timedelta64(int(day), "D")
    y, m, dd = str(d).split("-")
    return f"{dd}/{m}/{y}"


class NumericColumnsShard(Loaded):
    def __init__(self, cfg: dict, seed: int, devices, rehearse: bool):
        from elasticsearch_tpu.index.segment import (RangeIds, TpuSegment,
                                                     Uniform, numeric_column)
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.utils.shapes import pow2_bucket

        cfg = _sized(cfg, rehearse)
        self.cfg = cfg
        self.index = cfg["index"]
        n = int(cfg["documents"])
        if int(cfg["shards"]) != 1:
            raise ValueError("numeric_columns_shard loads one shard")
        D = pow2_bucket(n, minimum=64)
        trips = taxis.make_trips(n, seed)
        cols = cfg["columns"]
        exists = np.zeros(D, bool)
        exists[:n] = True

        def padded(values, dtype):
            out = np.zeros(D, dtype)
            out[:n] = values
            return out

        ms = padded((taxis.EPOCH_2015_S + trips["dropoff_s"].astype(np.int64))
                    * 1000, np.int64)
        numerics = {
            cols["date"]: numeric_column(cols["date"], "date", ms, exists),
            cols["distance"]: numeric_column(
                cols["distance"], "scaled_float",
                padded(trips["distance_cents"] / 100.0, np.float64), exists,
                scaling_factor=100),
            cols["amount"]: numeric_column(
                cols["amount"], "scaled_float",
                padded(trips["amount_cents"] / 100.0, np.float64), exists,
                scaling_factor=100)}
        del ms
        seg = TpuSegment(
            num_docs=n, max_docs=D, inverted={}, numerics=numerics,
            keywords={}, vectors={}, sources=Uniform(None, n),
            stored=Uniform(None, n), ids=RangeIds(0, n), id_map={},
            field_lengths={})
        node = Node(name="bench", data_path=cfg.get("data_path"))
        node.create_index(self.index, {
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": cfg["mappings"]}})
        node.indices[self.index].shards[0].engine.segments.append(seg)
        self.node = node
        self.cols = cols
        self.reference = AggReference(trips, taxis.EPOCH_2015_S, taxis.DAYS)
        self.pool = self._pool(cfg["queries"])
        self.pool_size = len(self.pool)
        self.n, self.D = n, D
        # bytes a slot of each column the program reads (its int32 code)
        # and of the live mask (int8)
        self.code_bytes = np.dtype(np.int32).itemsize
        self.live_bytes = np.dtype(np.int8).itemsize
        self.info = {"documents": n, "slots": D, "shards": 1,
                     "columns": sorted(numerics),
                     "pool": {"distance": sum(e[0] == "distance"
                                              for e in self.pool),
                              "date": sum(e[0] == "date" for e in self.pool)}}

    @staticmethod
    def _pool(q: dict) -> list:
        """Half (``distance``, 0, hi) entries, the track's own ``gte: 0``
        band with hi uniform over whole miles 5..50 (46 bodies, so they
        repeat: the request cache is off, and a repeat costs what the
        first did), half distinct (``date``, d0, n) windows of 2015
        (d0 + n <= 365), from the configuration's fixed seed."""
        rng = np.random.default_rng(int(q["pool_seed"]))
        half = int(q["pool"]) // 2
        lo_mi, hi_mi = q["distance_buckets"]
        his = rng.integers(lo_mi, hi_mi + 1, size=half)
        d_lo, d_hi = q["date_days"]
        windows = [(d0, w) for w in range(d_lo, d_hi + 1)
                   for d0 in range(taxis.DAYS - w + 1)]
        wpick = rng.choice(len(windows), int(q["pool"]) - half,
                           replace=False)
        pool = ([("distance", 0, int(hi)) for hi in his]
                + [("date", *windows[j]) for j in sorted(wpick)])
        order = rng.permutation(len(pool))
        return [pool[j] for j in order]

    def request(self, i: int) -> dict:
        e = self.pool[i]
        if e[0] == "distance":
            field = self.cols["distance"]
            return {"size": 0,
                    "query": {"bool": {"filter": {"range": {field: {
                        "lt": e[2], "gte": e[1]}}}}},
                    "aggs": {DIST_AGG: {
                        "histogram": {"field": field, "interval": 1},
                        "aggs": {AMOUNT_STATS: {"stats": {
                            "field": self.cols["amount"]}}}}}}
        field = self.cols["date"]
        return {"size": 0,
                "query": {"range": {field: {
                    "gte": _ddmmyyyy(e[1]), "lte": _ddmmyyyy(e[1] + e[2]),
                    "format": "dd/MM/yyyy"}}},
                "aggs": {DATE_AGG: {"date_histogram": {
                    "field": field, "interval": "day"}}}}

    def answer(self, reply: dict):
        if ("error" not in reply and not reply.get("timed_out")
                and isinstance(reply.get("aggregations"), dict)):
            return reply["aggregations"]
        return None

    def _as_reply(self, i: int, rows: list) -> dict:
        name = DIST_AGG if self.pool[i][0] == "distance" else DATE_AGG
        buckets = []
        for r in rows:
            b = {"key": r["key"], "doc_count": r["doc_count"]}
            if "stats" in r:
                b[AMOUNT_STATS] = r["stats"]
            buckets.append(b)
        return {name: {"buckets": buckets}}

    def compare(self, sample: list) -> dict:
        """``wrong_buckets``: buckets whose key is not the reference's or
        whose doc_count differs (a malformed answer counts all of its
        reference's); ``stat_err``: the widest relative gap of a returned
        count, sum, min, max or avg from the reference's."""
        wrong, err, faults = 0, 0.0, []
        for i, aggs in sample:
            want = self.reference.answer(self.pool[i])
            name = DIST_AGG if self.pool[i][0] == "distance" else DATE_AGG
            try:
                got = {float(b["key"]): b for b in aggs[name]["buckets"]}
                for b in got.values():
                    int(b["doc_count"])
            except (KeyError, TypeError, ValueError) as e:
                wrong += max(len(want), 1)
                if len(faults) < 5:
                    faults.append(f"pool entry {i}: malformed: {e!r}")
                continue
            miss = 0
            for r in want:
                b = got.pop(float(r["key"]), None)
                if b is None or int(b["doc_count"]) != r["doc_count"]:
                    miss += 1
                    continue
                if "stats" in r:
                    st = b.get(AMOUNT_STATS) or {}
                    for k, v in r["stats"].items():
                        g = st.get(k)
                        gap = (abs(float(g) - v) / max(abs(v), 1e-12)
                               if isinstance(g, (int, float)) else np.inf)
                        err = max(err, gap)
            miss += len(got)  # buckets the reference does not have
            if miss:
                wrong += miss
                if len(faults) < 5:
                    faults.append(f"pool entry {i} {self.pool[i]}: {miss} "
                                  f"bucket(s) wrong")
        return {"numbers": {"wrong_buckets": wrong, "stat_err": float(err)},
                "faults": faults, "compared": len(sample)}

    def control(self, pool: list) -> list:
        return [(i, self._as_reply(i, self.reference.answer(
            self.pool[i], bf16=True))) for i in pool]

    def work(self, i: int) -> dict:
        """One read of each column the request reads (its int32 code) and
        of the live mask; about one operation a document for each number
        reduced a bucket."""
        e = self.pool[i]
        cols, numbers = (2, 5) if e[0] == "distance" else (1, 1)
        return {"flop": float(self.n * numbers),
                "bytes": float(self.D * (cols * self.code_bytes
                                         + self.live_bytes)),
                "batch_bytes": 0.0}


load = NumericColumnsShard
