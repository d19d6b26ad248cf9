"""Device time a search of ``bm25_term_group_topk`` by the cap on the
width of the tail's ``[T, P]`` postings window (``search/context.TAIL_W``),
at the text cell's own size and over its own query pool: the microbench
that chose the cap (readings, PR 29's layout beside them: PERF.md §6,
PR 30).

One set-up through the benchmark's loader, then for every cap asked for,
with ``context.TAIL_W`` set to it and the product's own ``plan_term_group``
laying the windows out: the window slots a query over the whole pool and
the number of ``(R, T, P)`` program classes (host arithmetic); for a
seeded sample of the pool every class compiled once, then the sample
enqueued back to back and pulled at the end, ``--reps`` times — the device
is the bottleneck of such a loop, so wall time over searches is device
time a search; one query of each class alone (least of five blocking
calls) beside its slot count, for the per-slot cost; and whether every
cap's packed result is the first cap's bit for bit.

    chiprun -- python3 tools/tail_width_bench.py --out chiprun_out/tail_width
    JAX_PLATFORMS=cpu python3 tools/tail_width_bench.py --rehearse   # counts only

Needs a TPU unless ``--rehearse`` (the configuration's rehearsal size on
the CPU: no time is printed then).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def differences(first: list, got: list, k: int) -> dict:
    """How far a cap's packed results stand from the first cap's:
    results not equal bit for bit, of them those whose documents or
    total differ, and the widest gap between two scores at one rank as a
    share of the score."""
    from elasticsearch_tpu.ops.scoring import unpack_topk_result

    n_diff = n_docs = 0
    widest = 0.0
    for a, b in zip(first, got):
        if np.array_equal(a, b):
            continue
        n_diff += 1
        (va, ia, ta), (vb, ib, tb) = (unpack_topk_result(x, k)
                                      for x in (a, b))
        n_docs += int(not np.array_equal(ia, ib) or ta != tb)
        hit = np.isfinite(va) & np.isfinite(vb)
        widest = max(widest, float(np.max(
            np.abs(va[hit] - vb[hit]) / np.abs(va[hit]), initial=0.0)))
    return {"results_differing_from_the_first_cap": n_diff,
            "of_them_documents_or_total_differ": n_docs,
            "widest_score_gap_share": widest}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="msmarco-passage-shard")
    ap.add_argument("--seed", type=int, default=3000000001)
    ap.add_argument("--caps", default="8192,4096,2048")
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    from benchmarks import loaders
    from elasticsearch_tpu.ops.scoring import (bm25_term_group_topk,
                                               pack_term_group_words,
                                               topk_block_config)
    from elasticsearch_tpu.search import context
    from elasticsearch_tpu.search.queries import parse_query, plan_term_group

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): --rehearse for the counts",
              file=sys.stderr)
        return 3
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{args.config}.json")) as fh:
        cfg = json.load(fh)
    loaded = loaders.load(cfg, args.seed, jax.devices()[:1], args.rehearse)
    searcher = loaded.node.indices[loaded.index].shards[0].searcher
    seg = searcher.segments[0]
    ctx = context.SegmentContext(seg, searcher.mappings, searcher.analysis)
    k, blk = min(loaded.k, ctx.D), topk_block_config()

    queries = [parse_query(loaded.request(i)["query"])
               for i in range(loaded.pool_size)]
    rng = np.random.default_rng([args.seed, 0x7A11])
    sample = rng.choice(loaded.pool_size,
                        size=min(args.queries, loaded.pool_size),
                        replace=False).tolist()

    def call(plan):
        R = 0 if plan.impact is None else plan.qrows.shape[0]
        words = pack_term_group_words(plan.qrows, plan.qrw, plan.starts,
                                      plan.lens, plan.ws)
        key = (R, plan.starts.shape[0], plan.P)
        # R, T and P are plan_term_group's buckets  # tpulint: bucketed
        return key, lambda: bm25_term_group_topk(
            plan.impact, plan.inv.doc_ids, plan.inv.tfnorm, seg.live,
            seg.roots_dev if seg.has_nested else None, words,
            R=key[0], T=key[1], P=key[2], D=ctx.D, k=k, topk_block=blk)

    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "config": args.config, "seed": args.seed,
              "pool": loaded.pool_size, "sample": len(sample),
              "caps": {}}
    first = None
    for cap in args.caps.split(","):
        context.TAIL_W = int(cap)  # the constant under test
        plans = [plan_term_group(ctx, q) for q in queries]
        postings = [int(plan.lens.sum()) for plan in plans]
        pool_keys = [call(plan)[0] for plan in plans]
        slots = [T * P for _R, T, P in pool_keys]
        row = {"postings_mean": statistics.fmean(postings),
               "slots_mean": statistics.fmean(slots),
               "slots_median": statistics.median(slots),
               "slots_p90": sorted(slots)[int(0.9 * len(slots))],
               "fill": sum(postings) / sum(slots),
               "classes": len(set(pool_keys)),
               "sample_slots_mean": statistics.fmean(
                   slots[i] for i in sample)}
        calls = [call(plans[i]) for i in sample]
        one_of = {}
        for key, fn in calls:
            one_of.setdefault(key, fn)
        for fn in one_of.values():  # compile every class of the sample
            fn().block_until_ready()
        got = [np.asarray(fn()) for _key, fn in calls]
        if first is None:
            first = got
        row.update(differences(first, got, k))
        if not args.rehearse:
            per_search = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready([fn() for _key, fn in calls])
                per_search.append(
                    1e3 * (time.perf_counter() - t0) / len(calls))
            row["ms_a_search"] = per_search
            by_class = []
            for (R, T, P), fn in sorted(one_of.items()):
                best = float("inf")
                for _ in range(5):
                    t0 = time.perf_counter()
                    fn().block_until_ready()
                    best = min(best, 1e3 * (time.perf_counter() - t0))
                by_class.append([R, T, P, T * P, best])
            row["ms_by_class"] = by_class
        report["caps"][cap] = row
        print(cap, json.dumps({k_: v for k_, v in row.items()
                               if k_ != "ms_by_class"}), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "result.json"), "w") as fh:
            json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
