"""Headline bench through the PRODUCT path (round-3 verdict task 1).

Every timed number drives real product surfaces — `Node.search` (the mesh
query path: parse → compile → shard_map hybrid/scatter program → fetch),
`Node.msearch` (the batched fused kernel path, search/batch.py), and
`MeshSearchExecutor.search_knn` — over a 1M-doc MS-MARCO-shaped index and a
1M x 128 SIFT-shaped vector index. No raw-ops timing.

Prints ONE JSON line with the keys the driver records:
  {"metric", "value", "unit", "vs_baseline",
   "p50_ms", "p99_ms", "batched_qps", "mfu", ...}

- p50_ms/p99_ms: single-query Node.search latency on mixed Zipfian BM25
  queries (the honest unamortized product latency).
- p50_speedup_vs_cpu: CPU-reference p50 / TPU product-path p50 — evaluates
  BASELINE.json's ">=8x p50" target directly (`target_met`), un-massaged.
- batched_qps + vs_baseline (headline): a 2048-query pure-dense _msearch
  batch through Node.msearch (one fused qw@impact streaming-top-k per
  segment) vs the CPU reference's sequential throughput (1000/cpu_p50).
- mfu: model-flops-utilization of the batched kNN product call
  (2*Q*D*dims flops over measured wall time vs the chip's peak).
- ivf_recall_curve: recall@10 vs QPS through `knn {ann: true}` at several
  num_candidates, against exact numpy top-10 — PQ-vs-exact A/B rows
  ({num_candidates, path, recall_at_10, qps, fine_rank_k}) so the
  asymmetric coarse->fine pipeline is judged against the r05 fine-rank
  cliff on identical probes; `adc_dispatch` carries the ADC kernel
  counter deltas; `device` names platform, device kind and count.

CPU baseline (BASELINE.json `published` empty): in-process numpy reference
with identical Lucene-5 BM25 math — idf=ln(1+(N-df+0.5)/(df+0.5)), tfNorm
k1=1.2 b=0.75 — vectorized term-at-a-time scoring + argpartition top-k (a
stronger baseline than Lucene's per-doc iterators). Each query is timed
min-of-3 so `vs_baseline` stops swinging on machine noise (r3 verdict).

The corpus loads through the product's own segment structures
(index.segment.InvertedField/TpuSegment) built vectorized — 1M docs through
the per-doc Python parser would dominate the bench with non-search work —
then queries flow through the unmodified Node/search stack.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

K1, B = 1.2, 0.75

#: git-ignored scratch inside the checkout: the corpus cache and the
#: cold_start scenario's data + compile-cache directories live here
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_cache")

#: every metric lands here at measurement time; the record is this dict
PARTIAL: dict = {}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def stage(name: str):
    log(f"-- stage: {name}")


def refuse_fallback(device: dict) -> None:
    """A run that found no TPU fails, unless the CALLER asked for the CPU
    (``JAX_PLATFORMS=cpu`` in its environment): a record from a CPU this
    program fell back to is never printed."""
    if device["platform"] != "tpu" \
            and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise SystemExit(
            f"bench.py: JAX found platform [{device['platform']}], not a "
            f"TPU; set JAX_PLATFORMS=cpu to run on the CPU on purpose")


def emit_record(payload: dict) -> None:
    """The ONE stdout JSON line the driver records — always parseable."""
    base = {"metric": "bm25_batched_qps", "value": 0.0, "unit": "qps",
            "vs_baseline": 0.0}
    base.update(payload)
    print(json.dumps(base), flush=True)


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------

def build_corpus(n_docs: int, vocab: int, seed: int):
    """Term-major postings CSR for a Zipfian synthetic corpus
    (MS-MARCO-like: ~60-token passages, Zipf vocabulary). Deterministic in
    (n_docs, vocab, seed), so the ~2-minute build at the 1M default is
    disk-cached; a cache failure falls through to a fresh build."""
    # the version token guards the cache against generator/constant
    # changes (a K1/B or distribution tweak must not silently serve
    # corpora built by older code)
    ver = f"v1_k{K1}b{B}"
    cache = os.path.join(CACHE_DIR,
                         f"corpus_{ver}_{n_docs}_{vocab}_{seed}.npz")
    try:
        z = np.load(cache)
        return (z["u_doc"], z["tf"], z["tfn"], z["offsets"], z["df"],
                z["idf"], z["doc_len"])
    except Exception:
        pass
    rng = np.random.default_rng(seed)
    doc_len = np.clip(rng.normal(60, 15, n_docs), 20, 120).astype(np.int64)
    nnz_tok = int(doc_len.sum())
    terms = rng.zipf(1.15, nnz_tok).astype(np.int64)
    terms = np.where(terms >= vocab, rng.integers(1, vocab, nnz_tok), terms)
    docs = np.repeat(np.arange(n_docs, dtype=np.int64), doc_len)

    key = terms * n_docs + docs
    uniq, tf = np.unique(key, return_counts=True)
    u_term = (uniq // n_docs).astype(np.int32)
    u_doc = (uniq % n_docs).astype(np.int32)
    df = np.bincount(u_term, minlength=vocab).astype(np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)

    avg = doc_len.mean()
    tfn = (tf * (K1 + 1) / (tf + K1 * (1 - B + B * doc_len[u_doc] / avg))
           ).astype(np.float32)
    idf = np.log(1 + (n_docs - df + 0.5) / (df + 0.5)).astype(np.float32)
    tf = tf.astype(np.float32)
    try:
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        tmp = cache + f".{os.getpid()}.tmp.npz"  # savez keeps .npz names
        np.savez(tmp, u_doc=u_doc, tf=tf, tfn=tfn, offsets=offsets, df=df,
                 idf=idf, doc_len=doc_len)
        os.replace(tmp, cache)
    except Exception:
        pass  # cache is best-effort
    return u_doc, tf, tfn, offsets, df, idf, doc_len


def make_msmarco_node(u_doc, tf, tfn, offsets, df, doc_len, n_docs, vocab):
    """A real Node serving the corpus: the segment is built through the
    product's own structures (vectorized load) and injected into shard 0's
    engine; every query then flows through the unmodified search stack."""
    import jax

    from elasticsearch_tpu.index.segment import InvertedField, TpuSegment
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.utils.shapes import pad_to, pow2_bucket

    D = pow2_bucket(n_docs, minimum=64)
    nnz = u_doc.shape[0]
    nnz_pad = pow2_bucket(nnz, minimum=8)
    term_ids = np.repeat(np.arange(vocab, dtype=np.int32), df)
    inv = InvertedField(
        name="body",
        vocab={f"t{t}": t for t in range(vocab)},
        terms=[f"t{t}" for t in range(vocab)],
        df=df,
        cf=df.astype(np.int64),
        offsets=offsets,
        doc_ids=jax.device_put(pad_to(u_doc, nnz_pad, D)),
        tf=jax.device_put(pad_to(tf, nnz_pad, 0.0)),
        tfnorm=jax.device_put(pad_to(tfn, nnz_pad, 0.0)),
        term_ids=jax.device_put(pad_to(term_ids, nnz_pad, vocab)),
        nnz=nnz,
        num_docs=n_docs,
        total_terms=int(doc_len.sum()),
        avg_len=float(doc_len.mean()),
        doc_ids_host=u_doc,
        tfnorm_host=tfn,
        max_docs=D,
    )
    lens = np.zeros(D, np.float32)
    lens[:n_docs] = doc_len
    seg = TpuSegment(
        num_docs=n_docs, max_docs=D,
        inverted={"body": inv}, numerics={}, keywords={}, vectors={},
        sources=[None] * n_docs, stored=[None] * n_docs,
        ids=[str(i) for i in range(n_docs)], id_map={},
        field_lengths={"body": jax.device_put(lens)},
    )
    node = Node(name="bench")
    node.create_index("msmarco", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    node.indices["msmarco"].shards[0].engine.segments.append(seg)
    return node, seg


def make_sift_node(n_vecs: int, dims: int, seed: int):
    import jax

    from elasticsearch_tpu.index.segment import TpuSegment, VectorColumn
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.utils.shapes import pow2_bucket

    rng = np.random.default_rng(seed + 7)
    # SIFT-like: clustered enough that IVF probing is meaningful, with
    # within-cluster similarity gaps wide enough that bf16 MXU scoring
    # resolves true neighbors (SIFT1M's own gaps are comfortably > bf16 eps)
    n_clusters = 256
    cents = rng.standard_normal((n_clusters, dims)).astype(np.float32)
    assign = rng.integers(0, n_clusters, n_vecs)
    vecs = (cents[assign]
            + rng.standard_normal((n_vecs, dims)).astype(np.float32))
    D = pow2_bucket(n_vecs, minimum=64)
    vpad = np.zeros((D, dims), np.float32)
    vpad[:n_vecs] = vecs
    exists = np.zeros(D, bool)
    exists[:n_vecs] = True
    vc = VectorColumn(name="emb", vecs=jax.device_put(vpad),
                      exists=jax.device_put(exists), dims=dims,
                      vecs_host=vpad, exists_host=exists,
                      similarity="cosine")
    seg = TpuSegment(
        num_docs=n_vecs, max_docs=D,
        inverted={}, numerics={}, keywords={}, vectors={"emb": vc},
        sources=[None] * n_vecs, stored=[None] * n_vecs,
        ids=[str(i) for i in range(n_vecs)], id_map={},
        field_lengths={},
    )
    node = Node(name="bench-sift")
    node.create_index("sift", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "emb": {"type": "dense_vector", "dims": dims,
                    "similarity": "cosine",
                    "index_options": {"type": "ivf"}}}}})
    node.indices["sift"].shards[0].engine.segments.append(seg)
    return node, seg, vecs


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------

def make_queries(n_q: int, vocab: int, df: np.ndarray, seed: int,
                 terms_per_q: int = 4, dense_only=None):
    """Mixed Zipfian queries as term-id lists; `dense_only` (a bool[V] of
    dense-row membership) restricts sampling to dense terms."""
    rng = np.random.default_rng(seed + 1)
    qs = []
    pool = np.nonzero(dense_only)[0] if dense_only is not None else None
    for _ in range(n_q):
        npick = rng.integers(2, terms_per_q + 1)
        if pool is not None:
            t = rng.choice(pool, size=npick, replace=False)
        else:
            t = rng.zipf(1.3, npick).astype(np.int64)
            t = np.where((t >= vocab) | (df[np.clip(t, 0, vocab - 1)] == 0),
                         rng.integers(1, vocab, npick), t)
        qs.append(np.unique(t))
    return qs


def percentile_ms(times, p):
    return float(np.percentile(np.asarray(times) * 1000.0, p))


# ---------------------------------------------------------------------------
# cold_start scenario (ISSUE 14): restart A/B, pre-warm off vs on
# ---------------------------------------------------------------------------

#: child process driven three ways: seed (build + serve + persist census/
#: AOT blobs + close), off (restart with the whole zero-warmup pipeline
#: disabled), on (restart + census pre-warm + AOT/XLA caches). Every run
#: measures the FIRST nreq requests after boot — the restart cliff.
_COLD_CHILD = r'''
import json, os, sys, time
mode, data = sys.argv[1], sys.argv[2]
bodies, nreq = json.loads(sys.argv[3]), int(sys.argv[4])
from elasticsearch_tpu.utils.platform import enable_compilation_cache
if mode != "off":
    enable_compilation_cache()
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.monitor import compile_cache, programs
from elasticsearch_tpu.monitor.stats import device_label
t0 = time.perf_counter()
n = Node(name="cold-" + mode, data_path=data)
boot_ms = (time.perf_counter() - t0) * 1000.0
if mode == "seed":
    n.create_index("coldidx", {"mappings": {"properties": {
        "body": {"type": "text"}}}})
    svc = n.indices["coldidx"]
    ndocs = int(sys.argv[5])
    for i in range(ndocs):
        svc.index_doc(str(i), {"body": "common w%d w%d tail%d" % (
            i % 13, i % 7, i % 3)})
    svc.refresh()
    for b in bodies:
        assert n.search("coldidx", b)["hits"]["total"] >= 0
    n.close()  # persists census (keys + bodies) + AOT blobs stay on disk
    print("SEEDED")
    sys.exit(0)
warmup_ms, warmup_run = 0.0, None
if mode == "on":
    t0 = time.perf_counter()
    warmup_run = n.serving.warmup.run_index("coldidx", "bench")
    warmup_ms = (time.perf_counter() - t0) * 1000.0
lat = []
c0 = programs.REGISTRY.stats()["compiles"]
for i in range(nreq):
    b = bodies[i % len(bodies)]
    t0 = time.perf_counter()
    r = n.search("coldidx", b)
    lat.append((time.perf_counter() - t0) * 1000.0)
c1 = programs.REGISTRY.stats()["compiles"]
warm = {}
for row in n.metrics.summaries().get("estpu_search_duration_seconds", []):
    if row["labels"]["index"] == "coldidx":
        warm[row["labels"]["warmup"]] = row["count"]
print("RESULT " + json.dumps({
    "mode": mode, "boot_ms": round(boot_ms, 1),
    "warmup_ms": round(warmup_ms, 1), "warmup_run": warmup_run,
    "latencies_ms": [round(x, 3) for x in lat],
    "fresh_compiles_first_page": c1 - c0,
    "warm_counts": warm,
    "compile_cache": compile_cache.events_snapshot(),
    "device": device_label()}))
n.close()
'''


def run_cold_start(args) -> dict:
    """Cold-start restart A/B through REAL process boundaries: a seeded
    node persists its census + AOT executable blobs and dies; two fresh
    processes over the same data_path then serve the identical first
    ``--cold-requests`` requests — one with the zero-warmup pipeline
    disabled (ESTPU_WARMUP=0, ESTPU_AOT_CACHE=off, no compile cache),
    one with census pre-warm + the executable caches. p50/p99 of the
    first page is the restart cliff; the acceptance wants the `on` side
    at zero fresh compiles and zero warmup=true searches."""
    import shutil

    stage("cold-start")
    # one fixed path (the compile cache keys on it), emptied first so a
    # warm directory from an earlier run can never fake a cold start
    workdir = os.path.join(CACHE_DIR, "cold_start")
    shutil.rmtree(workdir, ignore_errors=True)
    data = os.path.join(workdir, "data")
    # a handful of padded shape classes (1/2/3-term queries, two k's):
    # enough programs that the compile cliff is visible, small enough
    # that the scenario stays minutes-free on CPU
    bodies = [{"query": {"match": {"body": t}}, "size": s}
              for t in ("common", "common w1", "w2 w5 tail1")
              for s in (5, 10)]
    xla_dir = os.path.join(workdir, "xla")

    def child(mode, extra_env=None):
        env = dict(os.environ)
        env.pop("ESTPU_WARMUP", None)
        env.pop("ESTPU_AOT_CACHE", None)
        env["JAX_COMPILATION_CACHE_DIR"] = xla_dir
        env.update(extra_env or {})
        if mode == "off":
            del env["JAX_COMPILATION_CACHE_DIR"]
        argv = [sys.executable, "-c", _COLD_CHILD, mode, data,
                json.dumps(bodies), str(args.cold_requests),
                str(args.cold_docs)]
        p = subprocess.run(argv, capture_output=True, text=True,
                           timeout=600, env=env)
        if p.returncode != 0:
            raise RuntimeError(
                f"cold_start child [{mode}] rc={p.returncode}: "
                f"{p.stderr.strip()[-400:]}")
        lines = [ln for ln in p.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        return json.loads(lines[-1][len("RESULT "):]) if lines else {}

    off_env = {"ESTPU_WARMUP": "0", "ESTPU_AOT_CACHE": "off"}
    try:
        log(f"cold_start: seeding {args.cold_docs} docs at {data}")
        child("seed")
        log("cold_start: restart with pre-warm OFF")
        off = child("off", off_env)
        log("cold_start: restart with pre-warm ON")
        on = child("on")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    def summarize(r):
        lat = r.get("latencies_ms") or [0.0]
        return {
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3),
            "first_request_ms": round(lat[0], 3),
            "boot_ms": r.get("boot_ms"),
            "warmup_ms": r.get("warmup_ms"),
            "fresh_compiles_first_page": r.get(
                "fresh_compiles_first_page"),
            "warm_counts": r.get("warm_counts"),
            "compile_cache": r.get("compile_cache"),
        }

    out = {
        "requests": args.cold_requests,
        "docs": args.cold_docs,
        "bodies": len(bodies),
        "device": on["device"],
        "off": summarize(off),
        "on": summarize(on),
        "warmup_run": on.get("warmup_run"),
    }
    o, w = out["off"], out["on"]
    if w["p99_ms"]:
        out["p99_improvement"] = round(o["p99_ms"] / w["p99_ms"], 2)
    if w["first_request_ms"]:
        out["first_request_improvement"] = round(
            o["first_request_ms"] / w["first_request_ms"], 2)
    out["zero_warmup_met"] = bool(
        w.get("fresh_compiles_first_page") == 0
        and (w.get("warm_counts") or {}).get("true", 0) == 0)
    log(f"cold_start: off p50/p99 {o['p50_ms']}/{o['p99_ms']} ms "
        f"(first {o['first_request_ms']} ms, "
        f"{o['fresh_compiles_first_page']} compiles) | on p50/p99 "
        f"{w['p50_ms']}/{w['p99_ms']} ms (first "
        f"{w['first_request_ms']} ms, "
        f"{w['fresh_compiles_first_page']} compiles) -> p99 "
        f"{out.get('p99_improvement')}x, zero_warmup_met="
        f"{out['zero_warmup_met']}")
    PARTIAL["cold_start"] = out
    return out


# sharded_qtf child: one process per side so the scatter side can never
# ride programs the mesh side compiled (and vice versa); under
# JAX_PLATFORMS=cpu the 8-device mesh emulation (XLA_FLAGS) binds before
# jax initializes.
_QTF_CHILD = '''
import json, os, random, sys, time
import numpy as np

mode, batches = sys.argv[1], json.loads(sys.argv[2])
docs, reps = int(sys.argv[3]), int(sys.argv[4])
if mode == "scatter":
    os.environ["ESTPU_DISABLE_MESH"] = "1"
from elasticsearch_tpu.monitor import kernels, programs
from elasticsearch_tpu.node import Node

WORDS = [f"w{i}" for i in range(32)]
n = Node()
n.create_index("sq", {"settings": {"number_of_shards": 8},
                      "mappings": {"properties": {
                          "body": {"type": "text"}}}})
svc = n.indices["sq"]
rng = random.Random(13)
for i in range(docs):
    svc.index_doc(str(i), {"body": " ".join(rng.choices(WORDS, k=8))})
svc.refresh()

def make_bodies(q):
    r = random.Random(100 + q)
    return [{"query": {"match": {"body": " ".join(
        r.sample(WORDS, r.randint(1, 3)))}}, "size": 10}
        for _ in range(q)]

def prog_key_counts():
    return {(e["program"], e["shapes"]):
            (e["compiles"], e["calls"],
             e["compile_seconds"], e["execute_seconds"])
            for e in programs.REGISTRY.snapshot()}

out = {}
for q in batches:
    pairs = [({"index": "sq"}, b) for b in make_bodies(q)]
    n.msearch(pairs)  # warm the shape class: compile stays out of timing
    before = prog_key_counts()
    kernels.reset()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        n.msearch(pairs)
        times.append(time.perf_counter() - t0)
    progs = {}
    for key, (c, x, cs, xs) in prog_key_counts().items():
        b = before.get(key, (0, 0, 0.0, 0.0))
        if (c, x) != (b[0], b[1]):
            progs["|".join(key)] = {
                "compiles": c - b[0], "executes": x - b[1],
                "compile_s": round(cs - b[2], 4),
                "execute_s": round(xs - b[3], 4)}
    snap = kernels.snapshot()
    out[str(q)] = {
        "wall_ms_per_batch": round(1000 * float(np.mean(times)), 3),
        "wall_ms_per_query": round(1000 * float(np.mean(times)) / q, 3),
        "kernels": {k: v for k, v in sorted(snap.items())
                    if "mesh" in k or "bm25" in k},
        "programs": progs}
from elasticsearch_tpu.monitor.stats import device_label
print("RESULT " + json.dumps({
    "mode": mode, "batch": out, "device": device_label()}))
n.close()
'''


def run_sharded_qtf(args) -> dict:
    """Mesh-collective query-then-fetch A/B (ISSUE 16): a coalesced
    msearch batch over an 8-shard index served by ONE shard_map device
    program per batch (mesh) vs the per-shard serial scatter loop
    (ESTPU_DISABLE_MESH=1), at batch sizes 1/16/64. Each side runs in
    its own process — over whatever devices it finds, or the emulated
    8-device mesh under JAX_PLATFORMS=cpu; the record carries
    per-program compile/execute deltas and the device each side saw. The
    acceptance wants mesh beating serial scatter at batch >= 16."""
    stage("sharded-qtf")
    batches = [1, 16, 64]
    docs = 4096

    def child(mode):
        env = dict(os.environ)
        if env.get("JAX_PLATFORMS", "").lower() == "cpu":
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count=8"
                                ).strip()
        p = subprocess.run(
            [sys.executable, "-c", _QTF_CHILD, mode, json.dumps(batches),
             str(docs), "5"],
            capture_output=True, text=True, timeout=600, env=env)
        if p.returncode != 0:
            raise RuntimeError(
                f"sharded_qtf child [{mode}] rc={p.returncode}: "
                f"{p.stderr.strip()[-400:]}")
        lines = [ln for ln in p.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        return json.loads(lines[-1][len("RESULT "):]) if lines else {}

    log(f"sharded_qtf: 8 shards, {docs} docs, batches {batches}, "
        "mesh vs serial scatter (one process each)")
    mesh = child("mesh")
    scatter = child("scatter")
    out = {
        "shards": 8,
        "docs": docs,
        "batches": batches,
        "device": mesh["device"],
        "mesh": mesh.get("batch", {}),
        "scatter": scatter.get("batch", {}),
    }
    speedup = {}
    for q in batches:
        m = out["mesh"].get(str(q), {}).get("wall_ms_per_batch")
        s = out["scatter"].get(str(q), {}).get("wall_ms_per_batch")
        if m and s:
            speedup[str(q)] = round(s / m, 2)
        log(f"sharded_qtf: batch={q} mesh {m} ms vs scatter {s} ms "
            f"-> {speedup.get(str(q))}x")
    out["speedup"] = speedup
    out["mesh_wins_at_16"] = bool(speedup.get("16", 0) > 1.0)
    PARTIAL["sharded_qtf"] = out
    return out


# ---------------------------------------------------------------------------
# hybrid_frontier scenario (ISSUE 19): recall@10/latency frontier of the
# fused hybrid pipeline vs each engine alone, identical probes
# ---------------------------------------------------------------------------

def run_hybrid_frontier(args) -> dict:
    """Planted-relevance A/B: each probe has 10 relevant docs whose
    signal is split across the channels (75% carry the probe's rare
    term, vectors sit near the probe centroid under noise) plus
    per-channel distractors (term-only and vector-only). BM25-only,
    kNN-only, and the fused hybrid (RRF at three weightings + linear)
    answer the SAME probes; each arm reports recall@10 against the
    planted set and p50 latency through the full product path. The
    fused path must actually serve stage 1 (kernel-counter-proven) and
    every arm's stage carries its backend label."""
    from elasticsearch_tpu.monitor import kernels as _kern
    from elasticsearch_tpu.node import Node

    stage("hybrid-frontier-build")
    rng = np.random.default_rng(args.seed + 19)
    n_docs, dims, n_q, k = 4096, min(args.dims, 64), 16, args.k
    n_rel, n_lex_noise, n_vec_noise = 10, 30, 30
    vecs = rng.standard_normal((n_docs, dims)).astype(np.float32)
    body_words = [" ".join(f"w{w}" for w in
                           rng.integers(0, 50, 3))
                  for _ in range(n_docs)]
    centroids = rng.standard_normal((n_q, dims)).astype(np.float32)
    relevant = []
    pool = rng.permutation(n_docs)
    take = 0
    for qi in range(n_q):
        rel = pool[take: take + n_rel]
        lexn = pool[take + n_rel: take + n_rel + n_lex_noise]
        vecn = pool[take + n_rel + n_lex_noise:
                    take + n_rel + n_lex_noise + n_vec_noise]
        take += n_rel + n_lex_noise + n_vec_noise
        relevant.append(set(int(i) for i in rel))
        for i in rel:
            if rng.random() < 0.75:  # lexical signal is NOISY
                body_words[i] += f" rel{qi}"
            vecs[i] = centroids[qi] + 0.55 * rng.standard_normal(dims)
        for i in lexn:  # term matches, vector doesn't
            body_words[i] += f" rel{qi}"
        for i in vecn:  # vector matches, term doesn't
            vecs[i] = centroids[qi] + 0.7 * rng.standard_normal(dims)

    node = Node(name="bench-hybrid")
    node.create_index("hyf", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "emb": {"type": "dense_vector", "dims": dims,
                    "similarity": "cosine"}}}})
    svc = node.indices["hyf"]
    for i in range(n_docs):
        svc.index_doc(str(i), {"body": body_words[i],
                               "emb": [float(x) for x in vecs[i]]})
    svc.refresh()

    def arm(name, bodies, runs=3):
        stage(f"hybrid-frontier-{name}")
        for b in bodies:  # warm every shape class
            node.search("hyf", b)
        times = np.full(len(bodies), np.inf)
        got = []
        for run in range(runs):
            for i, b in enumerate(bodies):
                t0 = time.perf_counter()
                r = node.search("hyf", b)
                times[i] = min(times[i], time.perf_counter() - t0)
                if run == 0:
                    got.append({int(h["_id"])
                                for h in r["hits"]["hits"]})
        rec = float(np.mean([len(g & relevant[qi]) / n_rel
                             for qi, g in enumerate(got)]))
        p50 = percentile_ms(times, 50)
        row = {"engine": name, "recall_at_10": round(rec, 3),
               "p50_ms": round(p50, 3),
               "qps": round(1000.0 / p50, 1) if p50 > 0 else 0.0}
        log(f"hybrid_frontier [{name}]: recall@10 {rec:.3f}, "
            f"p50 {p50:.2f} ms")
        return row

    nc = 100
    qv = [[float(x) for x in centroids[qi]] for qi in range(n_q)]

    def hybrid_bodies(method, weights):
        return [{"query": {"hybrid": {
            "query": {"match": {"body": f"rel{qi}"}},
            "knn": {"field": "emb", "query_vector": qv[qi], "k": k,
                    "num_candidates": nc},
            "fusion": {"method": method, "weights": list(weights),
                       "rank_constant": 60}}}, "size": k}
            for qi in range(n_q)]

    fused_before = _kern.snapshot().get("hybrid_fused_topk", 0)
    frontier = [
        arm("bm25", [{"query": {"match": {"body": f"rel{qi}"}},
                      "size": k} for qi in range(n_q)]),
        arm("knn", [{"query": {"knn": {
            "field": "emb", "query_vector": qv[qi], "k": k,
            "num_candidates": nc}}, "size": k} for qi in range(n_q)]),
        arm("hybrid_rrf_1_1", hybrid_bodies("rrf", (1.0, 1.0))),
        arm("hybrid_rrf_2_1", hybrid_bodies("rrf", (2.0, 1.0))),
        arm("hybrid_rrf_1_2", hybrid_bodies("rrf", (1.0, 2.0))),
        arm("hybrid_linear_1_1", hybrid_bodies("linear", (1.0, 1.0))),
    ]
    fused_served = _kern.snapshot().get("hybrid_fused_topk", 0) \
        - fused_before
    by = {r["engine"]: r for r in frontier}
    best_single = max(by["bm25"]["recall_at_10"],
                      by["knn"]["recall_at_10"])
    best_hybrid = max(r["recall_at_10"] for r in frontier
                      if r["engine"].startswith("hybrid"))
    out = {
        "frontier": frontier,
        "num_candidates": nc,
        "docs": n_docs, "dims": dims, "probes": n_q,
        "fused_stage1_calls": int(fused_served),
        "best_single_recall": best_single,
        "best_hybrid_recall": best_hybrid,
        "hybrid_wins": bool(best_hybrid > best_single
                            and fused_served > 0),
    }
    log(f"hybrid_frontier: best hybrid recall {best_hybrid:.3f} vs best "
        f"single-engine {best_single:.3f} "
        f"(fused stage-1 calls: {fused_served})")
    PARTIAL["hybrid_frontier"] = out
    node.close()
    return out


def bm25_product_latency(node, queries, k, runs=3):
    """Per-query Node.search wall time (the full product path)."""
    bodies = [{"query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
               "size": k} for q in queries]
    for b in bodies:  # warmup: compile every shape class
        node.search("msmarco", b)
    times = np.full(len(bodies), np.inf)
    for _ in range(runs):
        for i, b in enumerate(bodies):
            t0 = time.perf_counter()
            r = node.search("msmarco", b)
            times[i] = min(times[i], time.perf_counter() - t0)
    return times, r


def cpu_bm25_latency(u_doc, tfn, offsets, idf, queries, n_docs, k, runs=3):
    """Numpy reference: identical math, per-query times, min-of-runs."""
    times = np.full(len(queries), np.inf)
    tops = []
    for run in range(runs):
        for qi, q in enumerate(queries):
            t0 = time.perf_counter()
            scores = np.zeros(n_docs, np.float32)
            for t in q:
                s, e = int(offsets[t]), int(offsets[t + 1])
                if e > s:
                    scores[u_doc[s:e]] += idf[t] * tfn[s:e]
            top = np.argpartition(-scores, k)[:k]
            # Lucene tie order: equal scores rank by ascending doc id
            # (argsort alone leaves tie order to argpartition's arbitrary
            # layout, flapping the top-1 agreement probe on exact ties)
            top = top[np.lexsort((top, -scores[top]))]
            times[qi] = min(times[qi], time.perf_counter() - t0)
            if run == 0:
                # agreement-probe copy, OUTSIDE the timed region: widen
                # the partition so ties STRADDLING the k-th position also
                # resolve by ascending doc id (argpartition alone keeps an
                # arbitrary member of a boundary tie class)
                kw = min(k + 64, scores.shape[0] - 1)
                wide = np.argpartition(-scores, kw)[:kw]
                wide = wide[np.lexsort((wide, -scores[wide]))]
                tops.append(wide[:k])
    return times, tops


# fallback counters accumulated across the kernels.reset() calls below —
# the budget check at the end must see the WHOLE workload
FALLBACKS = {"mesh_fallback_total": 0, "span_clause_truncated": 0}


#: every kernel counter folded in before a scoped kernels.reset() —
#: metrics_delta reads reset-proof totals from here + the live snapshot
KERNELS_ACCUM: dict = {}


def harvest_fallbacks():
    from elasticsearch_tpu.monitor import kernels

    snap = kernels.snapshot()
    for key in FALLBACKS:
        FALLBACKS[key] += int(snap.get(key, 0))


def reset_kernels_scoped():
    """Reset the kernel-dispatch counters for a scoped measurement, but
    fold the current values into KERNELS_ACCUM first so the whole-run
    metrics_delta (executor cache hits/misses etc.) survives the reset."""
    from elasticsearch_tpu.monitor import kernels

    for k, v in kernels.snapshot().items():
        KERNELS_ACCUM[k] = KERNELS_ACCUM.get(k, 0) + v
    kernels.reset()


def batched_msearch_qps(node, queries, k):
    """One Node.msearch call: the fused batch product path."""
    from elasticsearch_tpu.monitor import kernels

    pairs = [({"index": "msmarco"},
              {"query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
               "size": k}) for q in queries]
    node.msearch(pairs)  # warmup at the FULL batch shape (jit is Q-static)
    harvest_fallbacks()
    reset_kernels_scoped()
    t0 = time.perf_counter()
    resp = node.msearch(pairs)
    dt = time.perf_counter() - t0
    snap = kernels.snapshot()
    served = snap.get("bm25_fused_topk", 0) + snap.get("bm25_hybrid", 0)
    if served < len(pairs):
        log(f"WARNING: msearch batch fell back to sequential "
            f"(batched={served}/{len(pairs)}) — batched_qps is unamortized")
    assert all(r["hits"]["total"] > 0 for r in resp["responses"][:4])
    return len(pairs) / dt, dt


def coalesced_qps(node, queries, k, n_threads=64):
    """N concurrent client threads issuing SINGLE-search bodies — no
    explicit ``_msearch`` — through the serving coalescer
    (serving/coalescer.py). Directly comparable to batched_msearch_qps
    on the same query set: the adaptive micro-batch queue must recover
    most of the explicit-batch amortization (acceptance: >= 80%).
    Returns (qps, dt, stats) where stats carries the coalescer's
    batch-size histogram delta and flush-reason counters."""
    import threading as _threading

    bodies = [{"query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
               "size": k} for q in queries]

    def run_round():
        errs = []
        cursor = {"i": 0}
        lock = _threading.Lock()

        def worker():
            while True:
                with lock:
                    i = cursor["i"]
                    if i >= len(bodies):
                        return
                    cursor["i"] = i + 1
                try:
                    node.search("msmarco", bodies[i])
                except Exception as e:  # a failed round must surface
                    errs.append(e)
                    return

        threads = [_threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]

    def _hist():
        rows = node.metrics.summaries().get(
            "estpu_coalescer_batch_size") or [{"count": 0,
                                               "sum_seconds": 0.0}]
        return rows[0]["count"], rows[0]["sum_seconds"]

    def _flushes():
        import re as _re

        out = {}
        for key, v in node.metrics.counter_values().items():
            m = _re.match(
                r'estpu_coalescer_flush_total\{reason="(\w+)"\}', key)
            if m:
                out[m.group(1)] = v
        return out

    run_round()  # warmup: compiles the pow2 batch shapes the queue emits
    harvest_fallbacks()
    reset_kernels_scoped()
    c0, s0 = _hist()
    f0 = _flushes()
    t0 = time.perf_counter()
    run_round()
    dt = time.perf_counter() - t0
    c1, s1 = _hist()
    f1 = _flushes()
    batches = c1 - c0
    stats = {
        "threads": n_threads,
        "batches": batches,
        "mean_batch": round((s1 - s0) / batches, 2) if batches else 0.0,
        "flush_reasons": {r: int(f1.get(r, 0) - f0.get(r, 0))
                          for r in f1 if f1.get(r, 0) - f0.get(r, 0)},
        "queue_wait": (node.metrics.summaries().get(
            "estpu_coalescer_queue_wait_seconds") or [{}])[0],
    }
    return len(bodies) / dt, dt, stats


def _msearch_top1(node, q):
    """Top-1 doc id for one query through the product path (agreement
    probe for the bf16-impact secondary measurement)."""
    r = node.search("msmarco", {
        "query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
        "size": 1})
    hits = r["hits"]["hits"]
    return hits[0]["_id"] if hits else None


def knn_product_latency(node, qvecs, k, ann=False, num_candidates=100,
                        pq=None):
    # ann (and pq) are passed EXPLICITLY both ways: the mapping's
    # index_options would otherwise route "exact" queries through
    # IVF/PQ silently, and the recall curve must A/B the two fine-rank
    # paths on identical probes
    bodies = [{"query": {"knn": {"field": "emb", "query_vector": [float(x) for x in qv],
                                 "k": k, "num_candidates": num_candidates,
                                 "ann": bool(ann),
                                 **({} if pq is None else {"pq": bool(pq)})}},
               "size": k} for qv in qvecs]
    for b in bodies[:4]:
        node.search("sift", b)
    times = []
    results = []
    for b in bodies:
        t0 = time.perf_counter()
        r = node.search("sift", b)
        times.append(time.perf_counter() - t0)
        results.append([int(h["_id"]) for h in r["hits"]["hits"]])
    return np.asarray(times), results


def knn_batched_mfu(node, n_q, dims, n_vecs, k, seed, reps=3):
    """Batched kNN through the MeshSearchExecutor product API (Q large
    enough that the matmul, not dispatch, dominates)."""
    ex = node.indices["sift"].mesh_executor()
    if ex is None:
        raise RuntimeError("sift index has no mesh executor")
    rng = np.random.default_rng(seed + 11)
    q = rng.standard_normal((n_q, dims)).astype(np.float32)
    ex.search_knn("emb", q, k=k)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        ex.search_knn("emb", q, k=k)
    dt = (time.perf_counter() - t0) / reps
    flops = 2.0 * n_q * n_vecs * dims
    return flops / dt, dt


def peak_flops_bf16():
    """Published bf16 peak FLOP/s of the chip under ``jax.devices()[0]``.
    A device that is not in the table is an error, not a default: a
    utilization against an unknown peak is not a number."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    table = [("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
             ("v6", 918e12), ("trillium", 918e12), ("v4", 275e12),
             ("v3", 123e12)]
    for key, f in table:
        if key in kind:
            return f
    raise ValueError(f"no bf16 peak known for device_kind [{kind}]")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=1 << 20)
    ap.add_argument("--vocab", type=int, default=30000)
    ap.add_argument("--vecs", type=int, default=1 << 20)
    ap.add_argument("--dims", type=int, default=128)
    ap.add_argument("--lat-queries", type=int, default=32)
    ap.add_argument("--batch-queries", type=int, default=2048)
    ap.add_argument("--knn-queries", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--skip-knn", action="store_true")
    ap.add_argument("--scenarios", default="core",
                    help="comma list of scenarios to run: core (the full "
                         "bm25/knn suite), cold_start (the ISSUE 14 "
                         "restart A/B), sharded_qtf (mesh vs scatter), "
                         "hybrid_frontier (ISSUE 19 fused-hybrid "
                         "recall/latency frontier) — each runs "
                         "standalone when named alone")
    ap.add_argument("--cold-docs", type=int, default=2048,
                    help="cold_start scenario corpus size (compile cost "
                         "is shape-bound, not data-bound — small keeps "
                         "the A/B honest and fast)")
    ap.add_argument("--cold-requests", type=int, default=100,
                    help="cold_start first-page request count (the "
                         "acceptance measures p50/p99 of these)")
    args = ap.parse_args()
    scenarios = {s.strip() for s in args.scenarios.split(",") if s.strip()}
    unknown = scenarios - {"core", "cold_start", "sharded_qtf",
                           "hybrid_frontier"}
    if unknown or not scenarios:
        ap.error(f"unknown --scenarios {sorted(unknown)}; "
                 "choose from: core, cold_start, sharded_qtf, "
                 "hybrid_frontier")

    payload: dict = {}
    # One process per chip: the scenarios whose work happens in CHILD
    # processes run first, while this parent has not touched JAX (a
    # parent that had would hold the chip and starve them). Each child
    # reports the device it ran on; a child that found no TPU without
    # JAX_PLATFORMS=cpu in its environment is refused below.
    if "cold_start" in scenarios:
        cold = run_cold_start(args)
        refuse_fallback(cold["device"])
        payload["cold_start"] = cold
        if scenarios == {"cold_start"}:
            # standalone cold_start: the headline IS the restart A/B
            payload.update({
                "metric": "cold_start_p99_improvement",
                "value": cold.get("p99_improvement", 0.0),
                "unit": "x",
                "vs_baseline": cold.get("p99_improvement", 0.0),
                "target_met": bool(cold.get("zero_warmup_met")),
                "device": cold["device"],
            })
    if "sharded_qtf" in scenarios:
        qtf = run_sharded_qtf(args)
        refuse_fallback(qtf["device"])
        payload["sharded_qtf"] = qtf
        if scenarios == {"sharded_qtf"}:
            # standalone: the headline is batch-16 mesh vs scatter
            payload.update({
                "metric": "sharded_qtf_speedup_batch16",
                "value": qtf.get("speedup", {}).get("16", 0.0),
                "unit": "x",
                "vs_baseline": qtf.get("speedup", {}).get("16", 0.0),
                "target_met": bool(qtf.get("mesh_wins_at_16")),
                "device": qtf["device"],
            })

    if scenarios & {"core", "hybrid_frontier"}:
        # from here on THIS process owns the chip; no child is started
        from elasticsearch_tpu.utils.platform import enable_compilation_cache

        enable_compilation_cache()  # amortize the per-shape compile zoo
        import jax

        from elasticsearch_tpu.monitor.stats import device_label

        device = device_label()
        refuse_fallback(device)
        log(f"devices: {jax.devices()}")
        if "core" in scenarios:
            payload.update(run_bench(args, jax))
        if "hybrid_frontier" in scenarios:
            hyf = run_hybrid_frontier(args)
            payload["hybrid_frontier"] = hyf
            if scenarios == {"hybrid_frontier"}:
                # standalone: the headline is fused recall vs the best
                # single engine on identical probes
                payload.update({
                    "metric": "hybrid_frontier_best_recall_at_10",
                    "value": hyf.get("best_hybrid_recall", 0.0),
                    "unit": "recall",
                    "vs_baseline": hyf.get("best_single_recall", 0.0),
                    "target_met": bool(hyf.get("hybrid_wins")),
                })
        payload["device"] = device
    emit_record(payload)


def run_bench(args, jax) -> dict:
    t_start = time.perf_counter()
    # continuous-metrics snapshot (monitor/metrics.py): the same counters
    # /_prometheus/metrics exposes, deltaed over the whole run so the
    # bench trajectory carries cache-hit/compile/eviction numbers
    from elasticsearch_tpu.monitor.metrics import (counters_delta,
                                                   process_counters)
    from elasticsearch_tpu.tracing import retrace

    # install the jit trace auditor BEFORE any ops module binds jax.jit,
    # so the delta's compile count covers the whole run (otherwise the
    # before-snapshot reads -1 = unknown and poisons the delta)
    retrace.ensure_installed()
    metrics_before = process_counters()
    stage("dispatch-floor")
    # per-call dispatch floor: the minimum round trip of ANY device call on
    # this host↔device link. Single-query latency can never beat a few
    # multiples of this — reported so p50 is read against the floor, not
    # assumed to be compute.
    tiny = jax.jit(lambda x: x + 1.0)
    tiny(0.0).block_until_ready()
    floors = []
    for _ in range(20):
        t0 = time.perf_counter()
        tiny(1.0).block_until_ready()
        floors.append(time.perf_counter() - t0)
    dispatch_floor_ms = float(np.percentile(np.asarray(floors) * 1000, 50))
    log(f"device dispatch floor (p50 of a trivial jitted call): "
        f"{dispatch_floor_ms:.2f} ms")
    PARTIAL["dispatch_floor_ms"] = round(dispatch_floor_ms, 3)
    stage("static-analysis")
    # tpulint self-measurement: rule findings + the pass-3 shapeflow
    # reach over the shipping tree ride the bench record, so a perf run
    # also documents the static health of the exact code it measured
    # (and the analyzer's own wall time is tracked release over release)
    try:
        t0 = time.perf_counter()
        from tools.tpulint import shapeflow as _shapeflow
        from tools.tpulint.project import build_project, lint_index

        _root = os.path.dirname(os.path.abspath(__file__))
        _idx, _errs = build_project(
            [os.path.join(_root, "elasticsearch_tpu"),
             os.path.join(_root, "tools"),
             os.path.join(_root, "bench.py")], root=_root)
        _found = lint_index(_idx) + _errs
        _rep = _shapeflow.analyze(_idx)
        _counts: dict = {}
        for _viol in _found:
            _counts[_viol.rule] = _counts.get(_viol.rule, 0) + 1
        PARTIAL["analysis"] = {
            "wall_s": round(time.perf_counter() - t0, 2),
            "rule_counts": dict(sorted(_counts.items())),
            "traced_fns": len(_idx.traced),
            "collective_fns": len(_idx.collective),
            "shapeflow_functions": _rep.functions,
            "shapeflow_factories": len(_rep.factories),
            "dims_classified": dict(_rep.dims_classified),
        }
        log(f"tpulint: {sum(_counts.values())} finding(s) in "
            f"{PARTIAL['analysis']['wall_s']}s; {_rep.functions} fns / "
            f"{len(_rep.factories)} factories in shapeflow reach")
    except Exception as e:  # the gate lives in CI; never sink a perf run
        PARTIAL["analysis"] = {"error": f"{type(e).__name__}: {e}"}
    stage("corpus-build")
    log(f"corpus: {args.docs} docs, vocab {args.vocab}")
    u_doc, tf, tfn, offsets, df, idf, doc_len = build_corpus(
        args.docs, args.vocab, args.seed)
    log(f"postings nnz: {u_doc.shape[0]} (built in "
        f"{time.perf_counter() - t_start:.1f}s)")
    stage("segment-device-transfer")
    node, seg = make_msmarco_node(u_doc, tf, tfn, offsets, df, doc_len,
                                  args.docs, args.vocab)

    # force the dense impact block now (product lazy build) so workloads see
    # the steady state; report its shape
    stage("dense-impact-block")
    block = seg.inverted["body"].dense_block()
    dense_rows = None
    if block is not None:
        dense_rows, impact = block
        log(f"dense impact block: F={impact.shape[0]} "
            f"({impact.shape[0] * impact.shape[1] * 4 >> 20} MB)")

    # -- single-query product latency (the headline) -------------------------
    stage("bm25-single-query-latency")
    lat_q = make_queries(args.lat_queries, args.vocab, df, args.seed)
    t0 = time.perf_counter()
    tpu_times, last = bm25_product_latency(node, lat_q, args.k)
    log(f"product latency pass done in {time.perf_counter() - t0:.1f}s; "
        f"sample total hits={last['hits']['total']}")
    p50, p99 = percentile_ms(tpu_times, 50), percentile_ms(tpu_times, 99)
    PARTIAL.update(p50_ms=round(p50, 3), p99_ms=round(p99, 3))

    stage("cpu-baseline")
    cpu_times, cpu_tops = cpu_bm25_latency(u_doc, tfn, offsets, idf, lat_q,
                                           args.docs, args.k)
    cpu_p50 = percentile_ms(cpu_times, 50)
    vs = cpu_p50 / p50 if p50 > 0 else 0.0
    log(f"bm25 single-query p50: tpu {p50:.2f} ms, p99 {p99:.2f} ms; "
        f"cpu p50 {cpu_p50:.2f} ms -> {vs:.1f}x (target >= 8x)")
    PARTIAL.update(cpu_p50_ms=round(cpu_p50, 3),
                   p50_speedup_vs_cpu=round(vs, 2),
                   target_p50_speedup=8.0, target_met=bool(vs >= 8.0))

    # correctness spot check: product top-1 vs numpy oracle top-1
    n_chk = min(16, len(lat_q))

    def top1_agreement(nd) -> int:
        got = 0
        for q, cpu_top in zip(lat_q[:n_chk], cpu_tops[:n_chk]):
            r = nd.search("msmarco", {
                "query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
                "size": 1})
            if r["hits"]["hits"] \
                    and int(r["hits"]["hits"][0]["_id"]) == cpu_top[0]:
                got += 1
        return got

    agree = top1_agreement(node)
    log(f"top-1 agreement vs numpy oracle: {agree}/{n_chk}")
    PARTIAL["top1_agreement"] = round(agree / max(n_chk, 1), 3)
    stage("tuned-single-query-latency")

    # SECONDARY: the tuned single-query config (ranking-grade matmul
    # precision + blocked top-k staging) on the SAME node — the knobs
    # are read at dispatch time and key every jit/program cache
    # (ops/scoring.py::impact_precision/topk_block_config), so flipping
    # the env compiles tuned programs next to the exact ones with no
    # second corpus in HBM. Clearly labeled: the headline p50 above
    # stays the untouched exact default.
    fast_env = {"ESTPU_IMPACT_PRECISION": "default",
                "ESTPU_BLOCKED_TOPK": "1"}
    old_env = {name: os.environ.get(name) for name in fast_env}
    os.environ.update(fast_env)
    p50_fast, fast_agree = 0.0, 0
    try:
        try:
            fast_times, _ = bm25_product_latency(node, lat_q, args.k)
            p50_fast = percentile_ms(fast_times, 50)
        except Exception as e:  # the secondary must never sink the capture
            log(f"tuned-config latency pass failed: {e}")
        if p50_fast > 0:
            try:
                fast_agree = top1_agreement(node)
            except Exception as e:  # keep the measured p50 regardless
                log(f"tuned-config agreement probe failed: {e}")
            log(f"tuned single-query p50 (prec=default + blocked topk): "
                f"{p50_fast:.2f} ms -> {cpu_p50 / p50_fast:.1f}x; top-1 "
                f"agreement {fast_agree}/{n_chk}")
    finally:
        for name, v in old_env.items():
            if v is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = v

    stage("tail-mode-ab")
    # A/B the single-query tail construction: candidate-set (TPU default;
    # scatter-free) vs the [D] scatter-add. Whichever loses informs the
    # auto default; the record carries both.
    _tm_old = os.environ.get("ESTPU_TAIL_MODE")
    try:
        mode = (_tm_old or "auto").lower()
        if mode == "auto":  # resolve the platform default being measured
            mode = ("candidates" if jax.default_backend() == "tpu"
                    else "scatter")
        other = "scatter" if mode == "candidates" else "candidates"
        os.environ["ESTPU_TAIL_MODE"] = other
        ab_times, _ = bm25_product_latency(node, lat_q, args.k)
        p50_ab = percentile_ms(ab_times, 50)
        log(f"tail-mode A/B ({other}): p50 {p50_ab:.2f} ms "
            f"(default-mode p50 {p50:.2f} ms)")
        PARTIAL[f"p50_ms_tail_{other}"] = round(p50_ab, 3)
    except Exception as e:  # secondary: never sink the capture
        log(f"tail-mode A/B failed: {e}")
    finally:
        if _tm_old is None:
            os.environ.pop("ESTPU_TAIL_MODE", None)
        else:
            os.environ["ESTPU_TAIL_MODE"] = _tm_old

    # -- batched product path ------------------------------------------------
    stage("batched-msearch")
    PARTIAL.update(
        p50_ms_tuned=round(p50_fast, 3),
        p50_speedup_vs_cpu_tuned=round(
            cpu_p50 / p50_fast if p50_fast > 0 else 0.0, 2),
        tuned_top1_agreement=round(fast_agree / max(n_chk, 1), 3))
    if dense_rows is not None:
        dense_mask = np.zeros(args.vocab, bool)
        dense_tids = np.nonzero(dense_rows >= 0)[0]
        dense_mask[dense_tids[dense_tids < args.vocab]] = True
        bat_q = make_queries(args.batch_queries, args.vocab, df, args.seed,
                             dense_only=dense_mask)
        batched_qps, bdt = batched_msearch_qps(node, bat_q, args.k)
        bm25_mfu_flops = 4.0 * len(bat_q) * impact.shape[0] * seg.max_docs
        log(f"batched msearch: {len(bat_q)} pure-dense queries in "
            f"{bdt * 1000:.0f} ms -> {batched_qps:.0f} qps")
        cpu_qps_now = 1000.0 / cpu_p50 if cpu_p50 > 0 else 1.0
        PARTIAL.update(batched_qps=round(batched_qps, 1),
                       value=round(batched_qps, 1),
                       vs_baseline=round(batched_qps / cpu_qps_now, 2))
        stage("batched-msearch-xla-ab")
        # A/B the batch kernel: the fused Pallas selection vs XLA's
        # chunked matmul + top_k (ESTPU_BM25_BATCH_KERNEL). Whichever
        # wins informs the default; both numbers land in the record.
        try:
            os.environ["ESTPU_BM25_BATCH_KERNEL"] = "xla"
            qps_xla, xdt = batched_msearch_qps(node, bat_q, args.k)
            log(f"batched msearch (XLA kernel): {len(bat_q)} queries in "
                f"{xdt * 1000:.0f} ms -> {qps_xla:.0f} qps "
                f"(pallas: {batched_qps:.0f})")
            PARTIAL["batched_qps_xla"] = round(qps_xla, 1)
        except Exception as e:  # the A/B must never sink the capture
            log(f"XLA batch A/B failed: {e}")
        finally:
            os.environ.pop("ESTPU_BM25_BATCH_KERNEL", None)
        stage("batched-msearch-mixed")
        # mixed Zipfian batch (rare-term scatter tails allowed): the
        # tier-2 hybrid batch path — realistic msearch traffic, not the
        # pure-dense best case
        mixed_q = make_queries(args.batch_queries, args.vocab, df,
                               args.seed + 9)
        batched_qps_mixed, mdt = batched_msearch_qps(node, mixed_q, args.k)
        log(f"batched msearch mixed: {len(mixed_q)} queries in "
            f"{mdt * 1000:.0f} ms -> {batched_qps_mixed:.0f} qps")
        PARTIAL["batched_qps_mixed"] = round(batched_qps_mixed, 1)
        stage("coalesced-qps")
        # cross-request coalescing (serving/): N concurrent clients
        # firing SINGLE-search bodies — no explicit _msearch — must
        # recover most of the explicit-batch amortization through the
        # adaptive micro-batch queue (ROADMAP item #1 acceptance >= 80%)
        try:
            co_qps, cdt, co_stats = coalesced_qps(node, bat_q, args.k)
            frac = co_qps / batched_qps if batched_qps else 0.0
            log(f"coalesced: {len(bat_q)} single-search bodies over "
                f"{co_stats['threads']} threads in {cdt * 1000:.0f} ms "
                f"-> {co_qps:.0f} qps ({frac * 100:.0f}% of explicit "
                f"msearch), mean batch {co_stats['mean_batch']}, "
                f"flushes {co_stats['flush_reasons']}")
            PARTIAL["coalesced_qps"] = round(co_qps, 1)
            PARTIAL["coalesced_vs_batched"] = round(frac, 3)
            PARTIAL["coalescer"] = co_stats
        except Exception as e:  # the scenario must never sink the capture
            log(f"coalesced_qps failed: {e}")
        stage("batched-msearch-bf16")
        # secondary: bf16-quantized impact block (SURVEY §6 lever) — same
        # batch, block rebuilt in bf16; report throughput AND top-1
        # agreement vs the f32 path so the quantization cost is visible
        import os as _os

        inv = seg.inverted["body"]
        sample = bat_q[:64]
        tops32 = [_msearch_top1(node, q) for q in sample]
        _os.environ["ESTPU_IMPACT_BF16"] = "1"
        try:
            with inv._dense_lock:
                # dropping the handle releases its fielddata-breaker
                # charge (resources/residency.py finalizer); the next
                # dense_block() rebuilds in bf16
                inv._dense = None
                inv._dense_host = None
            blk16 = inv.dense_block()
            if blk16 is not None:
                batched_qps_bf16, bdt16 = batched_msearch_qps(
                    node, bat_q, args.k)
                tops16 = [_msearch_top1(node, q) for q in sample]
                bf16_agree = float(np.mean([a == b for a, b in
                                            zip(tops32, tops16)]))
                log(f"batched msearch bf16 impacts: {bdt16 * 1000:.0f} ms "
                    f"-> {batched_qps_bf16:.0f} qps, top-1 agreement "
                    f"{bf16_agree:.3f}")
                PARTIAL.update(batched_qps_bf16=round(batched_qps_bf16, 1),
                               bf16_top1_agreement=round(bf16_agree, 3))
            else:
                batched_qps_bf16, bf16_agree = 0.0, 0.0
        finally:
            del _os.environ["ESTPU_IMPACT_BF16"]
    else:
        batched_qps, bm25_mfu_flops, bdt = 0.0, 0.0, 1.0
        batched_qps_bf16, bf16_agree = 0.0, 0.0
        batched_qps_mixed = 0.0
        log("no dense block — batched path skipped")

    # utilization is a device metric: on a CPU the caller asked for it is
    # not measured (None), never 0.0
    on_tpu = jax.default_backend() == "tpu"
    peak = peak_flops_bf16() if on_tpu else None
    PARTIAL["bm25_batched_mfu"] = (
        round(bm25_mfu_flops / bdt / peak, 4) if on_tpu else None)

    # -- kNN product path ----------------------------------------------------
    stage("knn-segment-build")
    knn = {}
    mfu = None
    if not args.skip_knn:
        sift_node, sift_seg, vecs = make_sift_node(args.vecs, args.dims,
                                                   args.seed)
        rng = np.random.default_rng(args.seed + 3)
        # queries near corpus points (recall is defined against real nbrs)
        qidx = rng.integers(0, args.vecs, args.knn_queries)
        qvecs = vecs[qidx] + 0.1 * rng.standard_normal(
            (args.knn_queries, args.dims)).astype(np.float32)

        stage("knn-exact-latency")
        times, got = knn_product_latency(sift_node, qvecs, args.k)
        knn["p50_ms"] = percentile_ms(times, 50)
        knn["p99_ms"] = percentile_ms(times, 99)
        PARTIAL["knn"] = knn  # knn dict mutations flow into the record

        # exact numpy reference (same metric: cosine)
        qs = qvecs / np.linalg.norm(qvecs, axis=1, keepdims=True)
        vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
        cpu_t = np.full(args.knn_queries, np.inf)
        exact = []
        for run in range(3):
            for i in range(args.knn_queries):
                t0 = time.perf_counter()
                sc = vn @ qs[i]
                top = np.argpartition(-sc, args.k)[: args.k]
                top = top[np.argsort(-sc[top])]
                cpu_t[i] = min(cpu_t[i], time.perf_counter() - t0)
                if run == 0:
                    exact.append(top)
        knn["cpu_p50_ms"] = percentile_ms(cpu_t, 50)
        knn["vs_cpu"] = knn["cpu_p50_ms"] / knn["p50_ms"]
        rec = np.mean([len(set(g) & set(e.tolist())) / args.k
                       for g, e in zip(got, exact)])
        knn["recall_at_10"] = float(rec)
        log(f"knn exact: tpu p50 {knn['p50_ms']:.2f} ms vs cpu "
            f"{knn['cpu_p50_ms']:.2f} ms ({knn['vs_cpu']:.1f}x), "
            f"recall@10 {rec:.3f}")

        stage("knn-batched-mfu")
        flops_rate, kdt = knn_batched_mfu(sift_node, 256, args.dims,
                                          args.vecs, args.k, args.seed)
        mfu = round(flops_rate / peak, 4) if on_tpu else None
        log(f"knn batched (executor.search_knn, Q=256): {kdt * 1000:.0f} ms, "
            f"mfu {mfu}")
        PARTIAL["mfu"] = mfu

        # IVF recall@10-vs-QPS curve through the product ANN path:
        # PQ-vs-exact A/B on identical probes. "exact" is the r05
        # fine-rank path (f32 re-score of EVERY probed candidate —
        # the measured 389 -> 12.6 qps cliff); "pq" is the asymmetric
        # coarse->fine pipeline (ADC over codes, exact re-rank of the
        # top fine_rank_k survivors only).
        stage("ivf-recall-curve")
        from elasticsearch_tpu.utils.shapes import pow2_bucket as _p2

        fine_rank_k = int(min(_p2(max(8 * args.k, 128)),
                              sift_seg.max_docs))
        curve = []
        from elasticsearch_tpu.monitor import kernels as _kern

        adc_before = {c: _kern.snapshot().get(c, 0)
                      for c in ("adc_pallas", "adc_xla", "knn_ivf_pq",
                                "adc_pallas_failed", "pq_build",
                                "pq_cache_hit")}
        for nc in (1000, 4000, 16000):
            for path, use_pq in (("exact", False), ("pq", True)):
                times, got = knn_product_latency(sift_node, qvecs, args.k,
                                                 ann=True,
                                                 num_candidates=nc,
                                                 pq=use_pq)
                r = np.mean([len(set(g) & set(e.tolist())) / args.k
                             for g, e in zip(got, exact)])
                curve.append({
                    "num_candidates": nc, "path": path,
                    "recall_at_10": round(float(r), 3),
                    "qps": round(1000.0 / percentile_ms(times, 50), 1),
                    "fine_rank_k": fine_rank_k if use_pq else None,
                })
                log(f"ivf nc={nc} [{path}]: recall@10 {r:.3f}, "
                    f"p50 {percentile_ms(times, 50):.2f} ms")
        knn["ivf_recall_curve"] = curve
        snap = _kern.snapshot()
        knn["adc_dispatch"] = {c: snap.get(c, 0) - v
                               for c, v in adc_before.items()}
        by_nc = {(row["num_candidates"], row["path"]): row for row in curve}
        exact16 = by_nc.get((16000, "exact"))
        pq16 = by_nc.get((16000, "pq"))
        if exact16 and pq16 and exact16["qps"] > 0:
            knn["pq_speedup_at_16k"] = round(pq16["qps"] / exact16["qps"], 2)
            log(f"pq speedup at nc=16000: {knn['pq_speedup_at_16k']}x "
                f"(recall {pq16['recall_at_10']})")

    # fallback budget (r4 verdict weak #5): the bench workload must be
    # served by the device product path — any host fallback or span
    # truncation on it is a regression, reported first-class
    harvest_fallbacks()
    mesh_fallback = FALLBACKS["mesh_fallback_total"]
    span_trunc = FALLBACKS["span_clause_truncated"]
    if mesh_fallback or span_trunc:
        log(f"WARNING: fallback budget exceeded — mesh_fallback_total="
            f"{mesh_fallback}, span_clause_truncated={span_trunc}")

    stage("steady-state-floor")
    # steady-state floor: the same trivial call AFTER the workload ran —
    # a host-device link can settle into a slower mode once large
    # transfers have occurred; p50 should be read against THIS floor,
    # not the pristine-session one
    floors = []
    for _ in range(20):
        t0 = time.perf_counter()
        tiny(1.0).block_until_ready()
        floors.append(time.perf_counter() - t0)
    floor_steady_ms = float(np.percentile(np.asarray(floors) * 1000, 50))
    log(f"steady-state dispatch floor: {floor_steady_ms:.2f} ms "
        f"(pristine was {dispatch_floor_ms:.2f} ms)")
    log(f"total bench wall time: {time.perf_counter() - t_start:.0f}s")
    # headline: batched product-path throughput vs the CPU reference's
    # sequential throughput (1000/cpu_p50). Single-query p50 and the
    # BASELINE >=8x p50 target are reported alongside, un-massaged.
    # the record IS the PARTIAL dict (every metric was written into it at
    # measurement time) plus the end-only fields
    metrics_after = process_counters()
    # re-add the kernel counts the scoped resets wiped (batched_msearch_qps
    # resets to attribute fallbacks; the run total must not lose them)
    for k, v in KERNELS_ACCUM.items():
        metrics_after[f"kernels.{k}"] = \
            metrics_after.get(f"kernels.{k}", 0.0) + v
    delta = counters_delta(metrics_before, metrics_after)
    PARTIAL["metrics_delta"] = {
        # the headline counters, named (executor cache economics, device
        # compiles, HBM tier churn) ...
        "executor_prep_hits": delta.get("kernels.executor_prep_hit", 0),
        "executor_prep_misses": delta.get("kernels.executor_prep_miss", 0),
        "executor_data_hits": delta.get("kernels.executor_data_hit", 0),
        "executor_data_misses": delta.get("kernels.executor_data_miss", 0),
        # null = trace auditor not installed (unknown, never a fake 0 and
        # never a -1 sentinel that leaks into sums)
        "jit_compiles": delta.get("jit.traces_total"),
        # AOT executable cache (parallel/aot.py): per-source resolution
        # counts + deserialize cost — null (not 0) while the AOT layer
        # never resolved, same typed-absence contract as jit_compiles
        "compile_cache_aot_hits": delta.get("compile_cache.aot_hit"),
        "compile_cache_xla_dir_hits": delta.get(
            "compile_cache.xla_dir_hit"),
        "compile_cache_fresh": delta.get("compile_cache.fresh"),
        "compile_cache_deserialize_seconds": delta.get(
            "compile_cache.deserialize_seconds"),
        "evictions": delta.get("residency.evictions", 0),
        "rehydrations": delta.get("residency.rehydrations", 0),
        "breaker_tripped": sum(
            v for k, v in delta.items()
            if k.startswith("breakers.") and v > 0),
        # stall watchdog (monitor/watchdog.py): a detector tripping (or
        # an incident dump captured) DURING a bench round is exactly the
        # kind of anomaly that silently corrupts a perf number — surface
        # it in the artifact, not only in the node's flight ring
        "watchdog_trips": delta.get("watchdog.trips", 0),
        "incidents": delta.get("watchdog.incidents", 0),
        # ... plus every other counter that moved during the run (None =
        # unavailable keys are dropped here; `jit_compiles` above carries
        # the typed null)
        "counters": {k: v for k, v in delta.items() if v},
    }
    # device-program observatory (monitor/programs.py): per-key
    # compile/execute deltas over the whole run — which programs this
    # workload compiled, what tracing+compilation cost vs cached
    # execution, ranked by execute time so the hot keys lead
    prog_delta = {
        k: v for k, v in delta.items()
        if k.startswith("programs.") and v
    }
    from elasticsearch_tpu.monitor import programs as _programs

    prog_rows = _programs.REGISTRY.snapshot()
    prog_rows.sort(key=lambda r: -r["execute_seconds"])
    PARTIAL["programs"] = {
        "backend": _programs.backend_fingerprint(),
        "totals": _programs.REGISTRY.stats(),
        "delta": prog_delta,
        "top_by_execute": [
            {k: r[k] for k in ("program", "shapes", "compiles",
                               "compile_seconds", "calls",
                               "execute_seconds", "execute_p50_seconds",
                               "execute_p99_seconds", "cold")}
            for r in prog_rows[:12]],
    }
    jc = PARTIAL['metrics_delta']['jit_compiles']
    log(f"metrics delta: prep {PARTIAL['metrics_delta']['executor_prep_hits']}"
        f"/{PARTIAL['metrics_delta']['executor_prep_misses']} hit/miss, "
        f"{'unknown' if jc is None else jc} jit traces, "
        f"{PARTIAL['metrics_delta']['evictions']} evictions; "
        f"programs: {PARTIAL['programs']['totals']}")
    cpu_qps = 1000.0 / cpu_p50 if cpu_p50 > 0 else 1.0
    PARTIAL.update({
        "metric": "bm25_batched_qps",
        "value": round(batched_qps, 1),
        "unit": "qps",
        "vs_baseline": round(batched_qps / cpu_qps, 2),
        "batched_qps": round(batched_qps, 1),
        "batched_qps_mixed": round(batched_qps_mixed, 1),
        "batched_qps_bf16": round(batched_qps_bf16, 1),
        "bf16_top1_agreement": round(bf16_agree, 3),
        "mfu": mfu,
        "dispatch_floor_steady_ms": round(floor_steady_ms, 3),
        "mesh_fallback_total": mesh_fallback,
        "span_clause_truncated": span_trunc,
        "fallback_budget_met": bool(mesh_fallback == 0 and span_trunc == 0),
        "docs": args.docs,
        "knn": knn,
    })
    return dict(PARTIAL)


if __name__ == "__main__":
    main()
