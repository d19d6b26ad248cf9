"""Recall@k of the approximate kNN program over a configuration's whole
query pool, at each ``num_candidates`` of a ladder: what a cell's
``num_candidates`` is chosen from. Loads the configuration as a run does
(data, index built at set-up through the program's own entry point),
then sends every pool query through the ANN branch's own program
(``ops/ivf.ivf_candidate_scores`` with the PQ tier, the live words and
the re-rank depth the branch uses) and takes the top k of its scores. The
exact neighbours come from a float32 scan of the resident slab in blocks
at full precision, re-scored in float64 on the host; a hit counts as
ANN-Benchmarks counts it (its distance within the k-th true neighbour's
plus 1e-3). A diagnostic for sizing a cell, not a measurement.

    python3 benchmarks/tools/ann_recall.py --config deep-image-96-ivfpq \\
        --seed 5 --num-candidates 1000,2000,5000,10000 --out ann_recall
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

QUERY_BLOCK = 256
ROW_BLOCK = 1 << 20
PER_BLOCK = 64


def exact_truth(loaded, qs, k):
    """(ids, cos) of the exact k + 5 nearest of each query, best first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    vc = loaded.node.indices[loaded.index].shards[0].engine.segments[0] \
        .vectors[loaded.field]
    slab, n = vc.vecs, loaded.n
    rb = min(ROW_BLOCK, slab.shape[0])

    @jax.jit
    def block_top(slab, q, lo):
        rows = jax.lax.dynamic_slice_in_dim(slab, lo, rb)
        norms = jnp.linalg.norm(rows, axis=1)
        cos = jnp.matmul(q, rows.T, precision=jax.lax.Precision.HIGHEST) \
            / jnp.maximum(norms, 1e-30)[None, :]
        live = (lo + jnp.arange(rb)) < n
        # TPU's partial reduction over-fetching PER_BLOCK a block (an exact
        # top_k sorts every row of the block); the float64 re-score below
        # orders what it finds
        vals, idx = jax.lax.approx_max_k(
            jnp.where(live[None, :], cos, -jnp.inf), PER_BLOCK,
            recall_target=0.999)
        return idx + lo

    host = loaded.reference.vecs
    out_ids, out_cos = [], []
    for s in range(0, len(qs), QUERY_BLOCK):
        q = qs[s:s + QUERY_BLOCK]
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        qd = jnp.asarray(qn)
        cand = np.concatenate([np.asarray(block_top(slab, qd, lo))
                               for lo in range(0, slab.shape[0], rb)
                               if lo < n], axis=1)
        for qi, row in zip(q, cand):
            v = host[row].astype(np.float64)
            q64 = qi.astype(np.float64)
            cos = (v @ q64) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q64))
            order = np.lexsort((row, -cos))[:k + 5]
            out_ids.append(row[order])
            out_cos.append(cos[order])
    return np.stack(out_ids), np.stack(out_cos)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="deep-image-96-ivfpq")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--num-candidates", default="1000,2000,5000,10000")
    ap.add_argument("--queries", type=int, default=0,
                    help="the first N pool queries at every num_candidates "
                         "after the first (0: the whole pool)")
    ap.add_argument("--max-search-s", type=float, default=0.2)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    else:
        from elasticsearch_tpu.utils.platform import enable_compilation_cache

        enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import contract, loaders
    from benchmarks.metrics import counters
    from benchmarks.reference.ann import SLACK
    from elasticsearch_tpu.ops.bitvec import pack_mask
    from elasticsearch_tpu.ops.ivf import ivf_candidate_scores
    from elasticsearch_tpu.ops.scoring import (_topk_with_mask_jit,
                                               topk_block_config)

    table = contract.load_table()
    entry = contract.config_of(table, args.config)
    with open(os.path.join(contract.ROOT, entry["file"])) as fh:
        cfg = json.load(fh)
    t0 = time.monotonic()
    loaded = loaders.load(cfg, args.seed, jax.devices()[:1], args.rehearse)
    report = {"device": jax.devices()[0].device_kind, "seed": args.seed,
              "load_s": time.monotonic() - t0, "loaded": loaded.info}
    print(json.dumps(report), flush=True)
    seg = loaded.node.indices[loaded.index].shards[0].engine.segments[0]
    vc = seg.vectors[loaded.field]
    D = seg.max_docs
    ivf, pq = vc.get_ivf(D), vc.get_pq(D)
    words = pack_mask(vc.exists & seg.live)
    qs = loaded.queries.astype(np.float32)
    k = loaded.k

    block = topk_block_config()

    def top(scores, mask):
        # the blocked top-k the search path's finish_topk uses (a flat
        # top_k over 2^24 slots takes ~39 ms on a v5e)
        return _topk_with_mask_jit(scores, mask, k=k, topk_block=block)[1]

    # a search through the node, and the program alone, before anything
    # long: a path that takes seconds a search stops the tool here
    nc0 = int(args.num_candidates.split(",")[0])
    times = {"node_search_s": [], "pack_mask_s": [], "program_s": [],
             "top_s": []}
    for i in range(12):
        t0 = time.monotonic()
        loaded.node.search(loaded.index, loaded.request(i))
        t1 = time.monotonic()
        pack_mask(vc.exists & seg.live).block_until_ready()
        t2 = time.monotonic()
        out = ivf_candidate_scores(
            ivf, vc.vecs, qs[i], nc0, vc.similarity, D, pq=pq,
            fine_k=loaded.fine_k, filter_words=words)
        jax.block_until_ready(out)
        t3 = time.monotonic()
        top(*out).block_until_ready()
        t4 = time.monotonic()
        for key, a, b in (("node_search_s", t0, t1), ("pack_mask_s", t1, t2),
                          ("program_s", t2, t3), ("top_s", t3, t4)):
            times[key].append(b - a)
    report["probe"] = {key: [round(x, 4) for x in v]
                       for key, v in times.items()}
    print(json.dumps(report["probe"]), flush=True)
    if np.median(times["node_search_s"][2:]) > args.max_search_s:
        print("a search takes longer than --max-search-s: stopping",
              flush=True)
        return 5
    t0 = time.monotonic()
    truth_ids, truth_cos = exact_truth(loaded, qs, k)
    report["truth_s"] = time.monotonic() - t0
    limit = (1.0 - truth_cos[:, k - 1]) + SLACK

    host = loaded.reference.vecs
    report["sweep"] = []
    for j, nc in enumerate(int(x) for x in args.num_candidates.split(",")):
        pool = len(qs) if not (j and args.queries) else min(args.queries,
                                                            len(qs))
        before = counters.parse(loaded.node.metrics.expose())
        t0 = time.monotonic()
        tops = [top(*ivf_candidate_scores(
            ivf, vc.vecs, q, nc, vc.similarity, D, pq=pq,
            fine_k=loaded.fine_k, filter_words=words)) for q in qs[:pool]]
        got = np.stack([np.asarray(t) for t in tops])
        secs = time.monotonic() - t0
        after = counters.parse(loaded.node.metrics.expose())
        recalls = []
        for i, ids in enumerate(got):
            v = host[ids].astype(np.float64)
            q64 = qs[i].astype(np.float64)
            cos = (v @ q64) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q64))
            recalls.append(float(np.mean((1.0 - cos) <= limit[i])))
        rec = np.asarray(recalls)
        row = {"num_candidates": nc, "nprobe": ivf.nprobe_for(nc),
               "queries": pool, "recall": float(rec.mean()),
               "recall_p05": float(np.quantile(rec, 0.05)),
               "recall_sd": float(rec.std()),
               "seconds": secs,
               "kernels": {key: after["estpu_kernel_dispatch_total"][key]
                           - before.get("estpu_kernel_dispatch_total",
                                        {}).get(key, 0.0)
                           for key in after.get("estpu_kernel_dispatch_total",
                                                {})
                           if key.startswith(('kernel="adc', 'kernel="ann'))}}
        report["sweep"].append(row)
        print(json.dumps(row), flush=True)
    report["memory_peak_bytes"] = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    print(json.dumps(report), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"recall-{args.seed}.json"),
                  "w") as fh:
            json.dump(report, fh, indent=1, default=str)
    loaded.node.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
