#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the REST search path still starts,
loads an index and answers right on the chip.

    python chip_smoke.py                      # needs a TPU; fails without one
    python chip_smoke.py --rehearse-cpu --docs 4096   # labelled platform: cpu

The parent process (this file's ``main``) never imports jax, nor anything
under ``elasticsearch_tpu`` that does: it talks HTTP and reads the device from
``GET /_nodes/stats``. It runs its children one after another, so exactly one
process owns the chip at any time.

* serve phase — ``python -m elasticsearch_tpu.server`` over a ``--data-path``:
  create an index (text, keyword, long, two 128-d dense_vector fields, one of
  them IVF+PQ) with one shard per device, ``_bulk`` a seeded Zipf corpus, one
  refresh and one ``_forcemerge``, then match / bool+filter+range / terms agg /
  exact kNN / ANN kNN / hybrid with MaxSim re-rank / one 256-body ``_msearch``
  / 64 single searches, then the same 64 at once (the coalescer, pinned to
  one fused batch) / a delete. Lexical and exact-kNN answers are held to a
  plain numpy reference written below (Lucene-5 BM25, cosine) on the same
  seeded data. SIGTERM, then a second server over the same data path reads
  the acknowledged documents back and answers the same requests — and
  compiles nothing fresh, from boot to exit.
* width phase — one child builds the 1,048,576-doc x 30,000-term segment and
  a 1,048,576 x 128 slab with bench.py's vectorised loader (``loaded_by:
  segment_loader``), serves them from a RestServer in that process, answers
  match / ``_msearch`` / kNN over the socket against the same reference, and
  compiles each of the three Pallas kernels, not interpreted, at the tiles its
  dispatcher picks for these shapes, comparing with the XLA twin.

Stdout is two lines: ``SMOKE_REPORT {...}`` (sizes, seconds, counters,
per-device bytes; also written to ``<out>/result.json``), then, last, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``. On
any failure nothing is printed there and the exit code is not 0. Numbers it
prints are a smoke's, not a baseline's.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
K1, B = 1.2, 0.75          # Lucene BM25Similarity defaults
VOCAB = 30_000             # bench.py's vocabulary
DIMS = 128
TOP_K = 10
FULL_DOCS = 1 << 20        # bench.py's product size
# score tolerance: the unit oracles compare device scores to numpy at
# rtol 1e-5..2e-5 (tests/unit/test_segment_and_scoring.py); the batched
# kNN tier scores through the bf16 Pallas kernel on a TPU, which the unit
# tests hold to top-1 agreement only
RTOL_SINGLE = 5e-5
RTOL_BATCHED = 2e-2

# counters that must stay at zero / must move, by family
ZERO_KERNELS = ("adc_pallas_failed", "maxsim_pallas_failed",
                "tail_scatter_free_failed",
                "mesh_fallback_total", "mesh_build_failed",
                "dist_mesh_fallback")
ZERO_CACHE = ("call_fallback", "deserialize_error", "store_error",
              "resolve_error", "corrupt_miss", "mismatch_miss")
ZERO_BYPASS = ("batch_error", "drain_error")


class SmokeFailure(Exception):
    """A phase did not do what it must; the run fails with this reason."""


def log(*a):
    print(f"[smoke {time.monotonic() - T0:7.1f}s]", *a, file=sys.stderr,
          flush=True)


T0 = time.monotonic()


def require(cond, why: str):
    if not cond:
        raise SmokeFailure(why)


# ---------------------------------------------------------------------------
# seeded data (the shape bench.py's build_corpus uses: ~60 Zipf tokens per
# passage over a 30,000-term vocabulary) + the plain numpy reference
# ---------------------------------------------------------------------------

def murmur3_routing(doc_id: str) -> int:
    """murmurhash3_x86_32 over the UTF-16LE bytes of the id, seed 0, as a
    signed int — Elasticsearch's Murmur3HashFunction, which decides the
    shard (``floorMod(hash, number_of_shards)``)."""
    data = doc_id.encode("utf-16-le")
    c1, c2, h = 0xCC9E2D51, 0x1B873593, 0
    n = len(data) // 4 * 4
    for i in range(0, n, 4):
        k = int.from_bytes(data[i:i + 4], "little")
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
        h = ((h << 13) | (h >> 19)) & 0xFFFFFFFF
        h = (h * 5 + 0xE6546B64) & 0xFFFFFFFF
    tail, k = data[n:], 0
    if tail:
        for j in range(len(tail) - 1, -1, -1):
            k = (k << 8) | tail[j]
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
    h ^= len(data)
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h - (1 << 32) if h >= (1 << 31) else h


class Corpus:
    """Seeded documents: ``body`` (Zipf tokens), ``tag`` (skewed keyword),
    ``n`` (long), ``vec``/``vec_ann`` (one clustered 128-d vector, sent
    under both names)."""

    def __init__(self, n_docs: int, seed: int):
        rng = np.random.default_rng(seed)
        self.n = n_docs
        self.doc_len = np.clip(rng.normal(60, 15, n_docs), 20,
                               120).astype(np.int64)
        nnz = int(self.doc_len.sum())
        terms = rng.zipf(1.15, nnz).astype(np.int64)
        self.terms = np.where(terms >= VOCAB,
                              rng.integers(1, VOCAB, nnz), terms)
        self.ptr = np.zeros(n_docs + 1, np.int64)
        self.ptr[1:] = np.cumsum(self.doc_len)
        self.tag = np.minimum(rng.geometric(0.08, n_docs) - 1, 63)
        self.num = rng.integers(0, 1_000_000, n_docs)
        n_clusters = 256
        cents = rng.standard_normal((n_clusters, DIMS))
        assign = rng.integers(0, n_clusters, n_docs)
        # 4 decimals: what travels as JSON text is exactly what the
        # reference scores (float64 text -> float32 on both sides)
        self.vecs = np.round(
            cents[assign] + rng.standard_normal((n_docs, DIMS)), 4)

    def source(self, i: int) -> dict:
        toks = self.terms[self.ptr[i]:self.ptr[i + 1]]
        vec = self.vecs[i].tolist()
        return {"body": " ".join(map(_TOKENS.__getitem__, toks.tolist())),
                "tag": f"c{int(self.tag[i])}", "n": int(self.num[i]),
                "vec": vec, "vec_ann": vec}


_TOKENS = [f"t{t}" for t in range(VOCAB)]


class Reference:
    """Plain numpy reference of what the index must answer: per-shard
    Lucene-5 BM25 (idf = ln(1 + (N - df + 0.5)/(df + 0.5)), tfNorm with
    k1=1.2 b=0.75 over exact field lengths, statistics local to each shard
    and counting every document the shard's segment holds) and cosine kNN
    scored as (1 + cos)/2."""

    def __init__(self, corpus: Corpus, n_shards: int):
        c = self.c = corpus
        self.S = n_shards
        if n_shards == 1:
            self.shard_of = np.zeros(c.n, np.int64)
        else:
            self.shard_of = np.fromiter(
                (murmur3_routing(str(i)) % n_shards for i in range(c.n)),
                np.int64, c.n)
        self.live = np.ones(c.n, bool)
        docs = np.repeat(np.arange(c.n, dtype=np.int64), c.doc_len)
        uniq, tf = np.unique(c.terms * c.n + docs, return_counts=True)
        self.u_doc = (uniq % c.n).astype(np.int64)
        u_term = uniq // c.n
        self.tf = tf.astype(np.float64)
        self.offsets = np.zeros(VOCAB + 1, np.int64)
        self.offsets[1:] = np.cumsum(np.bincount(u_term, minlength=VOCAB))
        self.n_shard = np.bincount(self.shard_of,
                                   minlength=n_shards).astype(np.float64)
        tot = np.bincount(self.shard_of, weights=c.doc_len,
                          minlength=n_shards)
        self.avgdl = tot / np.maximum(self.n_shard, 1)
        v32 = c.vecs.astype(np.float32)
        self.vn = v32 / np.linalg.norm(v32, axis=1, keepdims=True)

    def bm25(self, term_ids) -> np.ndarray:
        """f64[n] scores (0 = no match) for an OR of ``term_ids``."""
        c, scores = self.c, np.zeros(self.c.n, np.float64)
        for t in sorted(set(int(t) for t in term_ids)):
            s, e = int(self.offsets[t]), int(self.offsets[t + 1])
            if e == s:
                continue
            docs, tf = self.u_doc[s:e], self.tf[s:e]
            sh = self.shard_of[docs]
            df = np.bincount(sh, minlength=self.S).astype(np.float64)
            idf = np.log(1.0 + (self.n_shard - df + 0.5) / (df + 0.5))
            # the index computes tfnorm in float32 at freeze
            tfn = (tf * (K1 + 1.0) / (tf + K1 * (
                1.0 - B + B * c.doc_len[docs] / self.avgdl[sh]))
            ).astype(np.float32)
            scores[docs] += idf[sh] * tfn
        return scores

    def knn(self, q) -> np.ndarray:
        q32 = np.asarray(q, np.float32)
        cos = self.vn @ (q32 / np.linalg.norm(q32))
        return (1.0 + cos.astype(np.float64)) * 0.5


def check_topk(name: str, hits: list, ref_scores: np.ndarray,
               eligible: np.ndarray, k: int, rtol: float) -> bool:
    """Hold a top-k answer to the reference, up to ties inside ``rtol``:
    every returned doc is eligible and carries the reference's score for
    it, there are as many as there should be, in descending order, and no
    eligible doc left out scores above the last one returned. Returns
    whether the ids are also the reference's, in its order."""
    ids = [int(h["_id"]) for h in hits]
    got = np.asarray([h["_score"] for h in hits], np.float64)
    want_n = min(k, int(eligible.sum()))
    require(len(ids) == want_n,
            f"{name}: {len(ids)} hits, reference has {want_n}")
    require(len(set(ids)) == len(ids), f"{name}: duplicate hits {ids}")
    if not ids:
        return True
    require(bool(eligible[ids].all()),
            f"{name}: returned a doc the reference excludes: {ids}")
    want = ref_scores[ids]
    tol = rtol * np.maximum(np.abs(want), 1e-6)
    bad = np.abs(got - want) > tol
    require(not bad.any(),
            f"{name}: score mismatch on ids {np.asarray(ids)[bad].tolist()}:"
            f" got {got[bad].tolist()} want {want[bad].tolist()}")
    require(bool(np.all(np.diff(got) <= 1e-12)),
            f"{name}: hits not in descending score order")
    masked = np.where(eligible, ref_scores, -np.inf)
    masked[ids] = -np.inf
    best_left = float(masked.max())
    require(best_left <= got[-1] + rtol * max(abs(got[-1]), 1e-6),
            f"{name}: doc {int(masked.argmax())} (score {best_left}) beats "
            f"the last hit ({got[-1]}) but was not returned")
    order = np.lexsort((np.arange(ref_scores.size),
                        -np.where(eligible, ref_scores, -np.inf)))[:want_n]
    return ids == order.tolist()


# ---------------------------------------------------------------------------
# HTTP + child processes
# ---------------------------------------------------------------------------

class Http:
    """One keep-alive connection per thread to one server."""

    def __init__(self, port: int, timeout: float):
        self.port, self.timeout = port, timeout
        self._local = threading.local()

    def call(self, method: str, path: str, body=None, timeout=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        data = body.encode("utf-8") if isinstance(body, str) else body
        for attempt in (0, 1):
            conn = getattr(self._local, "conn", None)
            if conn is None:
                conn = self._local.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=timeout or self.timeout)
            conn.timeout = timeout or self.timeout
            if conn.sock is not None:
                conn.sock.settimeout(conn.timeout)
            try:
                conn.request(method, path, body=data,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                raw = resp.read()
                break
            except BaseException as e:
                # a failed exchange leaves the connection unusable
                conn.close()
                self._local.conn = None
                # a keep-alive connection the server closed: once more
                if attempt or not isinstance(e, (
                        http.client.RemoteDisconnected, BrokenPipeError,
                        ConnectionResetError)):
                    raise
        ctype = resp.getheader("Content-Type", "")
        if "json" in ctype:
            return resp.status, json.loads(raw)
        return resp.status, raw.decode("utf-8", "replace")

    def ok(self, method: str, path: str, body=None, timeout=None):
        status, out = self.call(method, path, body, timeout)
        require(200 <= status < 300,
                f"{method} {path} -> HTTP {status}: {str(out)[:600]}")
        return out


CHILDREN: list = []


def spawn(argv, log_path: str, env=None) -> subprocess.Popen:
    """Start a child in its own session (so its whole group can be
    stopped), output to ``log_path``."""
    fh = open(log_path, "ab")
    p = subprocess.Popen(argv, stdout=fh, stderr=subprocess.STDOUT,
                         cwd=HERE, env=env, start_new_session=True)
    fh.close()
    CHILDREN.append(p)
    return p


def stop_all():
    for p in CHILDREN:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            p.wait(timeout=30)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


class Server:
    """``python -m elasticsearch_tpu.server`` as a child, the normal entry
    point."""

    def __init__(self, name: str, data_path: str, out_dir: str, env: dict):
        self.name = name
        self.port = free_port()
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self.t_spawn = time.monotonic()
        self.proc = spawn(
            [sys.executable, "-m", "elasticsearch_tpu.server", "--port",
             str(self.port), "--name", name, "--data-path", data_path],
            self.log_path, env)
        self.http = Http(self.port, timeout=300.0)

    def wait_ready(self, boot_timeout: float) -> "Server":
        name, t0 = self.name, self.t_spawn
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{name} exited with code {self.proc.returncode} before "
                    f"listening:\n{tail(self.log_path)}")
            try:
                status, self.root = self.http.call("GET", "/", timeout=5.0)
                if status == 200:
                    break
            except (OSError, http.client.HTTPException):
                pass
            require(time.monotonic() - t0 < boot_timeout,
                    f"{name} did not listen within {boot_timeout:.0f}s:\n"
                    f"{tail(self.log_path)}")
            time.sleep(0.5)
        self.boot_seconds = time.monotonic() - t0
        log(f"{name} listening on :{self.port} after "
            f"{self.boot_seconds:.1f}s")
        return self

    def node_stats(self) -> dict:
        stats = self.http.ok("GET", "/_nodes/stats")
        (node,) = stats["nodes"].values()
        return node

    def metrics(self) -> dict:
        """/_prometheus/metrics as {family: {label-string: value}}."""
        text = self.http.ok("GET", "/_prometheus/metrics")
        out: dict = {}
        for line in text.splitlines():
            if not line or line[0] == "#":
                continue
            head, _, val = line.rpartition(" ")
            fam, _, labels = head.partition("{")
            try:
                out.setdefault(fam, {})[labels.rstrip("}")] = float(val)
            except ValueError:
                pass
        return out

    def stop(self) -> float:
        """SIGTERM, wait for a clean exit; seconds it took."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name} did not exit within 120s of "
                               f"SIGTERM:\n{tail(self.log_path)}")
        require(rc == 0, f"{self.name} exited with code {rc} on SIGTERM:\n"
                         f"{tail(self.log_path)}")
        return time.monotonic() - t0


def by_label(family: dict, label: str) -> dict:
    """{kernel="x"} rows of one family -> {x: value}."""
    out = {}
    for labels, v in (family or {}).items():
        for part in labels.split(","):
            k, _, val = part.partition("=")
            if k == label:
                out[val.strip('"')] = v
    return out


def counters(server: Server) -> dict:
    m = server.metrics()
    progs = m.get("estpu_program_compiles_total", {})
    return {
        "kernels": {k: int(v) for k, v in by_label(
            m.get("estpu_kernel_dispatch_total"), "kernel").items()},
        "compile_cache": {k: int(v) for k, v in by_label(
            m.get("estpu_compile_cache_events_total"), "source").items()},
        "program_keys": len(progs),
        "program_compiles": int(sum(progs.values())),
        "program_compile_seconds": round(sum(
            m.get("estpu_program_compile_seconds", {}).values()), 3),
        "program_execute_seconds": round(sum(
            m.get("estpu_program_execute_seconds", {}).values()), 3),
        "jit_traces": int(sum(m.get("estpu_jit_traces_total",
                                    {}).values())),
        "coalescer": {
            "flush": {k: int(v) for k, v in by_label(
                m.get("estpu_coalescer_flush_total"), "reason").items()},
            "bypass": {k: int(v) for k, v in by_label(
                m.get("estpu_coalescer_bypass_total"), "reason").items()},
            "batches": int(sum(m.get("estpu_coalescer_batch_size_count",
                                     {}).values())),
            "batched_requests": int(sum(m.get(
                "estpu_coalescer_batch_size_sum", {}).values())),
        },
    }


def fresh_programs(server: Server) -> list:
    """Program keys this process compiled at full price (the ``cache``
    column of ``_cat/programs``)."""
    rows = server.http.ok("GET", "/_cat/programs?format=json")
    return [f"{r.get('program')}|{r.get('shapes')}" for r in rows
            if "fresh" in str(r.get("cache", ""))]


def device_of(node_stats: dict) -> dict:
    acc = node_stats["accelerator"]
    return {"platform": acc["platform"], "kind": acc["device_kind"],
            "count": acc["device_count"]}


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

INDEX = "smoke"


def create_index(srv: Server, n_shards: int):
    vec = {"type": "dense_vector", "dims": DIMS, "similarity": "cosine"}
    srv.http.ok("PUT", f"/{INDEX}", {
        "settings": {"number_of_shards": n_shards, "number_of_replicas": 0},
        # no _all catch-all field: it would tokenize and invert every
        # value a second time
        "mappings": {"_all": {"enabled": False}, "properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"},
            "n": {"type": "long"}, "vec": vec,
            "vec_ann": dict(vec, index_options={"type": "ivf_pq"})}}})


def bulk_load(srv: Server, corpus: Corpus, batch: int = 2048) -> float:
    """``_bulk`` every document; bodies are built one batch ahead on a
    second thread so the server never waits for this process."""
    def build(lo: int) -> bytes:
        lines = []
        for i in range(lo, min(lo + batch, corpus.n)):
            lines.append('{"index":{"_id":"%d"}}' % i)
            lines.append(json.dumps(corpus.source(i),
                                    separators=(",", ":")))
        return ("\n".join(lines) + "\n").encode("utf-8")

    t0 = time.monotonic()
    with ThreadPoolExecutor(1) as pool:
        nxt = pool.submit(build, 0)
        for lo in range(0, corpus.n, batch):
            body = nxt.result()
            if lo + batch < corpus.n:
                nxt = pool.submit(build, lo + batch)
            resp = srv.http.ok("POST", f"/{INDEX}/_bulk", body,
                               timeout=600.0)
            require(resp.get("errors") is False,
                    f"_bulk at {lo}: errors={resp.get('errors')} "
                    f"{str(resp)[:400]}")
            want = min(batch, corpus.n - lo)
            require(len(resp["items"]) == want
                    and all(it["index"]["status"] == 201
                            for it in resp["items"]),
                    f"_bulk at {lo}: not every document was created")
            if (lo // batch) % 16 == 0:
                log(f"  _bulk {lo + want}/{corpus.n}")
    return time.monotonic() - t0


def search(srv: Server, body: dict, path=f"/{INDEX}/_search") -> dict:
    r = srv.http.ok("POST", path, body)
    check_shards(r, str(body)[:120])
    return r


def check_shards(r: dict, what: str):
    require(r.get("timed_out") is False and r["_shards"]["failed"] == 0
            and r["_shards"]["successful"] == r["_shards"]["total"],
            f"partial answer to {what}: {r.get('_shards')} "
            f"timed_out={r.get('timed_out')}")


def make_requests(corpus: Corpus, seed: int) -> dict:
    """The fixed request set, a function of the seed alone."""
    rng = np.random.default_rng(seed + 1)

    def zipf_terms(lo=2, hi=4):
        t = rng.zipf(1.3, int(rng.integers(lo, hi + 1))).astype(np.int64)
        t = np.where(t >= VOCAB, rng.integers(1, VOCAB, t.size), t)
        return sorted(set(int(x) for x in t))

    def text(terms):
        return " ".join(f"t{t}" for t in terms)

    def near(i):  # a query vector near document i
        return np.round(corpus.vecs[i]
                        + 0.1 * rng.standard_normal(DIMS), 4)

    reqs = {
        "match": [zipf_terms() for _ in range(6)],
        "bool": [(zipf_terms(), int(rng.integers(0, 8)),
                  *sorted(int(x) for x in rng.integers(0, 1_000_000, 2)))
                 for _ in range(3)],
        "agg": [zipf_terms(1, 2) for _ in range(2)],
        # default num_candidates (100 > the streaming kernel's k gate) and
        # 64 (inside it): both selection paths
        "knn": [(near(int(rng.integers(0, corpus.n))), nc)
                for nc in (None, 64, None, 64)],
        "ann": [near(int(rng.integers(0, corpus.n))) for _ in range(4)],
        "hybrid": [(zipf_terms(), near(int(rng.integers(0, corpus.n))),
                    np.round(rng.standard_normal((8, DIMS)), 4))
                   for _ in range(2)],
        "msearch": [zipf_terms() for _ in range(256)],
    }
    reqs["concurrent"] = reqs["msearch"][:64]
    reqs["text"] = text
    return reqs


def match_body(text, terms, size=TOP_K):
    return {"query": {"match": {"body": text(terms)}}, "size": size,
            "_source": False}


def run_requests(srv: Server, ref: Reference, reqs: dict, tag: str) -> dict:
    """Send the request set, hold every lexical and exact-kNN answer to
    the reference. Returns per-kind summaries."""
    text = reqs["text"]
    live = ref.live
    out: dict = {}

    def lexical(name, hits_obj, terms, rtol, eligible_extra=None):
        scores = ref.bm25(terms)
        eligible = live & (scores > 0)
        if eligible_extra is not None:
            eligible &= eligible_extra
        require(hits_obj["total"] == int(eligible.sum()),
                f"{name}: hits.total {hits_obj['total']} != reference "
                f"{int(eligible.sum())}")
        return check_topk(name, hits_obj["hits"], scores, eligible, TOP_K,
                          rtol)

    t0 = time.monotonic()
    eq = [lexical(f"{tag} match[{i}]",
                  search(srv, match_body(text, t))["hits"], t, RTOL_SINGLE)
          for i, t in enumerate(reqs["match"])]
    out["match"] = {"n": len(eq), "ids_equal": sum(eq)}

    eq = []
    for i, (terms, tagno, lo, hi) in enumerate(reqs["bool"]):
        body = {"query": {"bool": {
            "must": [{"match": {"body": text(terms)}}],
            "filter": [{"term": {"tag": f"c{tagno}"}},
                       {"range": {"n": {"gte": lo, "lt": hi}}}]}},
            "size": TOP_K, "_source": False}
        extra = ((ref.c.tag == tagno) & (ref.c.num >= lo)
                 & (ref.c.num < hi))
        eq.append(lexical(f"{tag} bool[{i}]", search(srv, body)["hits"],
                          terms, RTOL_SINGLE, extra))
    out["bool_filter_range"] = {"n": len(eq), "ids_equal": sum(eq)}

    for i, terms in enumerate(reqs["agg"]):
        r = search(srv, {"size": 0, "query": {"match": {
            "body": text(terms)}}, "aggs": {"tags": {"terms": {
                "field": "tag", "size": 64}}}})
        matched = live & (ref.bm25(terms) > 0)
        want = np.bincount(ref.c.tag[matched], minlength=64)
        got = {b["key"]: b["doc_count"]
               for b in r["aggregations"]["tags"]["buckets"]}
        require(r["hits"]["total"] == int(matched.sum())
                and got == {f"c{j}": int(n) for j, n in enumerate(want)
                            if n},
                f"{tag} terms agg[{i}]: buckets differ from the reference")
    out["terms_agg"] = {"n": len(reqs["agg"])}

    has_vec = live
    eq = []
    for i, (q, nc) in enumerate(reqs["knn"]):
        knn = {"field": "vec", "query_vector": q.tolist(), "k": TOP_K}
        if nc:
            knn["num_candidates"] = nc
        r = search(srv, {"query": {"knn": knn}, "size": TOP_K,
                         "_source": False})
        eq.append(check_topk(f"{tag} knn[{i}]", r["hits"]["hits"],
                             ref.knn(q), has_vec, TOP_K,
                             RTOL_SINGLE))
    out["knn_exact"] = {"n": len(eq), "ids_equal": sum(eq)}

    recalls = []
    for i, q in enumerate(reqs["ann"]):
        r = search(srv, {"query": {"knn": {
            "field": "vec_ann", "query_vector": q.tolist(), "k": TOP_K,
            "num_candidates": 2000}}, "size": TOP_K, "_source": False})
        sc = np.where(has_vec, ref.knn(q), -np.inf)
        exact = set(np.argsort(-sc)[:TOP_K].tolist())
        hits = r["hits"]["hits"]
        require(len(hits) == TOP_K, f"{tag} ann[{i}]: {len(hits)} hits")
        for h in hits:  # survivors are re-scored exactly
            require(abs(h["_score"] - sc[int(h["_id"])])
                    <= RTOL_SINGLE * abs(sc[int(h["_id"])]),
                    f"{tag} ann[{i}]: score of {h['_id']} is not its exact "
                    f"similarity")
        recalls.append(len(exact & {int(h["_id"]) for h in hits}) / TOP_K)
    require(float(np.mean(recalls)) >= 0.5,
            f"{tag} ann recall@10 {recalls} against exact is below 0.5")
    out["knn_ann"] = {"n": len(recalls),
                      "recall_at_10": round(float(np.mean(recalls)), 3)}

    for i, (terms, q, toks) in enumerate(reqs["hybrid"]):
        r = search(srv, {"query": {"hybrid": {
            "query": {"match": {"body": text(terms)}},
            "knn": {"field": "vec_ann", "query_vector": q.tolist(),
                    "k": TOP_K, "num_candidates": 100},
            "fusion": {"method": "rrf", "weights": [1.0, 1.0],
                       "rank_constant": 60},
            "rerank": {"query_vectors": toks.tolist(),
                       "window_size": 64}}},
            "size": TOP_K, "_source": False})
        hits = r["hits"]["hits"]
        require(len(hits) == TOP_K and all(
            np.isfinite(h["_score"]) and live[int(h["_id"])] for h in hits),
            f"{tag} hybrid[{i}]: bad hits {hits}")
        require((r.get("hybrid") or {}).get("rerank") == "applied",
                f"{tag} hybrid[{i}]: re-rank stage not applied: "
                f"{r.get('hybrid')}")
    out["hybrid_rerank"] = {"n": len(reqs["hybrid"])}

    # every body that will be batched below is first answered on its own.
    # (On a mesh a batched body joins the program census and is replayed
    # singly at the next boot — serving/warmup.py; this pass is what
    # compiles and stores that shape, so the restarted server's replay
    # finds it.)
    eq = [lexical(f"{tag} single[{i}]",
                  search(srv, match_body(text, t))["hits"], t, RTOL_SINGLE)
          for i, t in enumerate(reqs["msearch"])]
    out["singles_256"] = {"n": len(eq), "ids_equal": sum(eq)}

    lines = []
    for terms in reqs["msearch"]:
        lines.append(json.dumps({"index": INDEX}))
        lines.append(json.dumps(match_body(text, terms)))
    r = srv.http.ok("POST", "/_msearch", "\n".join(lines) + "\n")
    require(len(r["responses"]) == len(reqs["msearch"]),
            f"{tag} _msearch: {len(r['responses'])} responses")
    eq = []
    for i, (resp, terms) in enumerate(zip(r["responses"],
                                          reqs["msearch"])):
        require("error" not in resp, f"{tag} _msearch[{i}]: {resp}")
        check_shards(resp, f"_msearch[{i}]")
        eq.append(lexical(f"{tag} _msearch[{i}]", resp["hits"], terms,
                          RTOL_BATCHED))
    out["msearch_256"] = {"n": len(eq), "ids_equal": sum(eq)}

    # the coalescer: 64 of those bodies as concurrent single searches.
    # Left to its adaptive window, how many batches racing singles make,
    # of which pow2 sizes and of which bodies, is a matter of timing, so
    # the restarted server could meet a batch shape the first never
    # compiled. The smoke pins the documented serving.coalescer.* settings
    # and sends the bodies in waves the search pool can hold at once: a
    # wave parks until all of it is in and runs as one fused batch, the
    # same bodies and the same shape in every process.
    conc = reqs["concurrent"]
    threads = srv.node_stats()["thread_pool"]["search"]["threads"]
    wave = 1 << (min(len(conc), threads).bit_length() - 1)
    require(wave >= 2, f"search pool of {threads} threads cannot coalesce")
    co0 = counters(srv)["coalescer"]
    srv.http.ok("PUT", "/_cluster/settings", {"transient": {
        "serving.coalescer.mode": "always",
        "serving.coalescer.max_batch": wave,
        "serving.coalescer.max_wait": "20s",
        "serving.coalescer.idle_gap": "20s"}})
    try:
        eq = []
        with ThreadPoolExecutor(wave) as pool:
            for lo in range(0, len(conc), wave):
                futures = [pool.submit(search, srv, match_body(text, terms))
                           for terms in conc[lo:lo + wave]]
                eq += [lexical(f"{tag} concurrent[{lo + i}]",
                               f.result(timeout=600)["hits"], terms,
                               RTOL_BATCHED)
                       for i, (f, terms) in enumerate(
                           zip(futures, conc[lo:lo + wave]))]
    finally:
        srv.http.ok("PUT", "/_cluster/settings", {"transient": {
            "serving.coalescer.mode": None,
            "serving.coalescer.max_batch": None,
            "serving.coalescer.max_wait": None,
            "serving.coalescer.idle_gap": None}})
    co1 = counters(srv)["coalescer"]
    flushes = {k: v - co0["flush"].get(k, 0)
               for k, v in co1["flush"].items() if v != co0["flush"].get(k)}
    fused = co1["batches"] - co0["batches"]
    # a wave with a body the fused tiers refuse (all its terms rare) runs
    # one by one, by design; which waves those are is fixed by the seed
    require(flushes == {"full": len(conc) // wave} and fused >= 1,
            f"{tag} concurrent: {len(conc)} singles in waves of {wave} "
            f"flushed as {flushes}, {fused} of them fused; bypasses "
            f"{co1['bypass']}")
    out["concurrent_64"] = {
        "n": len(eq), "ids_equal": sum(eq), "wave": wave,
        "fused_batches": fused,
        "fused_requests": co1["batched_requests"] - co0["batched_requests"]}
    out["seconds"] = round(time.monotonic() - t0, 2)
    return out


VICTIM_TOKEN = "zzvictim"


def victim_source(corpus: Corpus, reqs: dict) -> dict:
    """One more document, written after the load was frozen: it carries a
    token nothing else has, and the first match request's terms."""
    src = corpus.source(0)
    src["body"] = " ".join([VICTIM_TOKEN] + [reqs["text"](
        reqs["match"][0])] * 3)
    return src


def write_then_delete(srv: Server, ref: Reference, reqs: dict,
                      corpus: Corpus):
    """Index one document into a segment of its own, find it, delete it:
    it must be returned while it lives — scored by its own segment's
    statistics (N = df = 1, its length the average) — and never after."""
    vid, terms = corpus.n, reqs["match"][0]
    src = victim_source(corpus, reqs)
    r = srv.http.ok("PUT", f"/{INDEX}/_doc/{vid}", src)
    require(r.get("created") is True, f"index of {vid}: {r}")
    srv.http.ok("POST", f"/{INDEX}/_refresh")
    own = {"query": {"match": {"body": VICTIM_TOKEN}}, "_source": False}
    hits = search(srv, own)["hits"]
    require(hits["total"] == 1 and hits["hits"][0]["_id"] == str(vid),
            f"the document written after the freeze is not found: {hits}")
    tfn = np.float32(3.0 * (K1 + 1.0) / (3.0 + K1))
    v_score = len(terms) * float(np.log(1.0 + 0.5 / 1.5)) * float(tfn)
    scores = np.append(ref.bm25(terms), v_score)
    eligible = np.append(ref.live & (scores[:-1] > 0), True)
    hits = search(srv, match_body(reqs["text"], terms))["hits"]
    require(hits["total"] == int(eligible.sum()),
            f"hits.total {hits['total']} does not count the new document")
    check_topk("with-new-doc", hits["hits"], scores, eligible, TOP_K,
               RTOL_SINGLE)
    r = srv.http.ok("DELETE", f"/{INDEX}/_doc/{vid}")
    require(r.get("found") is True, f"delete of {vid}: {r}")
    srv.http.ok("POST", f"/{INDEX}/_refresh")
    gone_check(srv, ref, reqs, corpus, "server-1")


def gone_check(srv: Server, ref: Reference, reqs: dict, corpus: Corpus,
               tag: str):
    """The deleted document does not come back, and the answer without it
    is the reference's."""
    own = {"query": {"match": {"body": VICTIM_TOKEN}}, "_source": False}
    hits = search(srv, own)["hits"]
    require(hits["total"] == 0 and not hits["hits"],
            f"{tag}: the deleted document is still returned: {hits}")
    terms = reqs["match"][0]
    scores = ref.bm25(terms)
    hits = search(srv, match_body(reqs["text"], terms))["hits"]
    require(hits["total"] == int((ref.live & (scores > 0)).sum()),
            f"{tag}: hits.total {hits['total']} still counts the deleted "
            f"document")
    check_topk(f"{tag} after-delete", hits["hits"], scores,
               ref.live & (scores > 0), TOP_K, RTOL_SINGLE)
    status, _ = srv.http.call("GET", f"/{INDEX}/_doc/{corpus.n}")
    require(status == 404, f"{tag}: GET of the deleted doc -> {status}")


def wait_warmup(srv: Server, timeout: float) -> dict:
    """The restarted server replays its program census on its own
    (serving/warmup.py, queued before it listens); let it finish so what
    the requests compile can be told from what the replay compiled."""
    t0 = time.monotonic()
    while True:
        st = srv.http.ok("GET", "/_warmup")
        if not st.get("queued") and not st.get("active"):
            return (st.get("runs") or {}).get(INDEX) or {}
        require(time.monotonic() - t0 < timeout,
                f"pre-warm still running after {timeout:.0f}s: {st}")
        time.sleep(0.5)


def read_back(srv: Server, corpus: Corpus):
    """Every acknowledged document is there after the restart."""
    n = srv.http.ok("POST", f"/{INDEX}/_count", {})["count"]
    require(n == corpus.n, f"_count after restart {n} != {corpus.n}")
    rng = np.random.default_rng(7)
    for i in rng.integers(0, corpus.n, 24).tolist():
        doc = srv.http.ok("GET", f"/{INDEX}/_doc/{i}")
        require(doc["_source"] == corpus.source(i),
                f"doc {i} read back differs from what was acknowledged")


def serve_phase(args, env, out_dir, work_dir, result):
    data_path = os.path.join(work_dir, "data")
    srv = Server("smoke-1", data_path, out_dir, env)
    corpus = Corpus(args.docs, args.seed)  # while the server boots
    log(f"corpus: {corpus.n} docs, {int(corpus.doc_len.sum())} tokens")
    reqs = make_requests(corpus, args.seed)
    srv.wait_ready(180.0)
    ns = srv.node_stats()
    device = device_of(ns)
    result["device"] = device
    result["devices_reported"] = srv.root.get("devices")
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        srv.stop()
        raise SmokeFailure(
            f"JAX found no accelerator: the server reports platform "
            f"[{device['platform']}] ({srv.root.get('devices')}); "
            f"chip_smoke.py needs a TPU (--rehearse-cpu is the labelled "
            f"CPU rehearsal)")
    n_shards = device["count"]
    log(f"device: {device}; index with {n_shards} shard(s)")
    result["native_codec"] = ns["native"]["codec"]
    # what the breakers budget against: 70% of a static 16 GiB base
    # (resources/breakers.hbm_capacity), printed beside what the devices
    # report as bytes_limit
    result["breaker_parent_limit"] = ns["breakers"]["parent"][
        "limit_size_in_bytes"]

    create_index(srv, n_shards)
    c0 = counters(srv)
    with ThreadPoolExecutor(1) as pool:  # the reference builds meanwhile
        ref_future = pool.submit(Reference, corpus, n_shards)
        ingest_s = bulk_load(srv, corpus)
        ref = ref_future.result()
    t0 = time.monotonic()
    srv.http.ok("POST", f"/{INDEX}/_refresh", timeout=1200.0)
    freeze_s = time.monotonic() - t0
    t0 = time.monotonic()
    srv.http.ok("POST", f"/{INDEX}/_forcemerge", timeout=1200.0)
    merge_s = time.monotonic() - t0
    n = srv.http.ok("POST", f"/{INDEX}/_count", {})["count"]
    require(n == corpus.n, f"_count after load {n} != {corpus.n}")
    c1 = counters(srv)
    result["load"] = {
        "loaded_by": "_bulk", "docs": corpus.n,
        "ingest_seconds": round(ingest_s, 2),
        "ingest_docs_per_s": round(corpus.n / ingest_s, 1),
        "freeze_seconds": round(freeze_s, 2),
        "forcemerge_seconds": round(merge_s, 2),
        "compiles": c1["program_compiles"] - c0["program_compiles"],
        "compile_seconds": round(c1["program_compile_seconds"]
                                 - c0["program_compile_seconds"], 3),
        "jit_traces": c1["jit_traces"] - c0["jit_traces"],
    }
    log(f"load: {result['load']}")

    first = run_requests(srv, ref, reqs, "server-1")
    write_then_delete(srv, ref, reqs, corpus)
    c2 = counters(srv)
    first["compile_seconds"] = round(
        c2["program_compile_seconds"] - c1["program_compile_seconds"], 3)
    first["execute_seconds"] = round(
        c2["program_execute_seconds"] - c1["program_execute_seconds"], 3)
    first["compiles"] = c2["program_compiles"] - c1["program_compiles"]
    result["server_1"] = {"boot_seconds": round(srv.boot_seconds, 2),
                          "requests": first, "counters": c2,
                          "devices": srv.node_stats()["accelerator"][
                              "devices"]}
    log(f"server-1 requests: {first}")
    result["server_1"]["stop_seconds"] = round(srv.stop(), 2)

    srv = Server("smoke-2", data_path, out_dir, env).wait_ready(
        args.deadline - (time.monotonic() - T0))  # translog replay
    require(device_of(srv.node_stats()) == device,
            "second server reports another device")
    read_back(srv, corpus)
    warm = wait_warmup(srv, 600.0)
    c_boot = counters(srv)
    # replay rebuilds the segment the first server froze (the late
    # document and its delete cancel out), so every request below has the
    # shapes — and the answers — it had there
    second = run_requests(srv, ref, reqs, "server-2")
    gone_check(srv, ref, reqs, corpus, "server-2")
    c3 = counters(srv)
    second["compile_seconds"] = c3["program_compile_seconds"]
    second["execute_seconds"] = c3["program_execute_seconds"]
    result["server_2"] = {"boot_seconds": round(srv.boot_seconds, 2),
                          "prewarm": {k: warm[k] for k in (
                              "status", "replayed", "errors", "took_ms")
                              if k in warm},
                          "compile_cache_at_boot": c_boot["compile_cache"],
                          "requests": second, "counters": c3,
                          "fresh_programs": fresh_programs(srv),
                          "devices": srv.node_stats()["accelerator"][
                              "devices"]}
    log(f"server-2 requests: {second}")
    result["server_2"]["stop_seconds"] = round(srv.stop(), 2)


def judge_serve(result: dict):
    """The counter half of the verdict (the checks above already raised
    on any wrong answer)."""
    on_tpu = result["device"]["platform"] == "tpu"
    for name in ("server_1", "server_2"):
        k = result[name]["counters"]["kernels"]
        cc = result[name]["counters"]["compile_cache"]
        for key in ZERO_KERNELS:
            require(k.get(key, 0) == 0, f"{name}: {key} = {k.get(key)}")
        for key in ZERO_CACHE:
            require(cc.get(key, 0) == 0,
                    f"{name}: compile cache {key} = {cc.get(key)}")
        bypass = result[name]["counters"]["coalescer"]["bypass"]
        for key in ZERO_BYPASS:  # a batch that failed and ran one by one
            require(bypass.get(key, 0) == 0,
                    f"{name}: coalescer bypass {key} = {bypass.get(key)}")
        require(k.get("mesh_search", 0) > 0, f"{name}: mesh_search = 0")
        require(k.get("knn_ivf_pq", 0) > 0, f"{name}: knn_ivf_pq = 0")
        for key in (("adc_pallas", "maxsim_adc_pallas") if on_tpu
                    else ("adc_xla", "maxsim_adc_xla")):  # the rehearsal
            require(k.get(key, 0) > 0, f"{name}: {key} = 0")
    # the restart: everything the second server ran — its census replay
    # at boot, the repeated requests, the coalesced batch — the first had
    # compiled and stored, so that process compiles nothing at full price
    s2 = result["server_2"]
    cc = s2["counters"]["compile_cache"]
    s2["fresh_at_boot"] = s2["compile_cache_at_boot"].get("fresh", 0)
    s2["fresh_total"] = cc.get("fresh", 0)
    require(s2["fresh_total"] == 0,
            f"second server compiled {s2['fresh_total']} programs fresh "
            f"({s2['fresh_at_boot']} of them before its first request): "
            f"{s2['fresh_programs']}")
    require(cc.get("xla_dir_hit", 0) + cc.get("aot_hit", 0) > 0,
            "second server resolved no program from a cache")


# ---------------------------------------------------------------------------
# width phase (one child process; imports jax)
# ---------------------------------------------------------------------------

def width_child(args) -> int:
    """Runs in its own process: the 1M-doc segment and 1M x 128 slab
    through bench.py's vectorised loader, served by a RestServer here,
    checked over the socket; then the three Pallas kernels against their
    XLA twins. Prints one ``WIDTH_RESULT {...}`` line."""
    sys.path.insert(0, HERE)
    import bench

    from elasticsearch_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.monitor.stats import device_label
    from elasticsearch_tpu.ops import pallas_kernels as pk
    from elasticsearch_tpu.rest.server import RestServer

    device = device_label()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(f"width child: platform [{device['platform']}] is not a TPU",
              file=sys.stderr)
        return 3
    interpret = not on_tpu
    out: dict = {"loaded_by": "segment_loader", "docs": args.width_docs,
                 "vocab": VOCAB, "dims": DIMS, "device": device}
    n = args.width_docs
    t0 = time.monotonic()
    u_doc, tf, tfn, offsets, df, idf, doc_len = bench.build_corpus(
        n, VOCAB, args.seed)
    out["corpus_seconds"] = round(time.monotonic() - t0, 2)
    t0 = time.monotonic()
    node, seg = bench.make_msmarco_node(u_doc, tf, tfn, offsets, df,
                                        doc_len, n, VOCAB)
    block = seg.inverted["body"].dense_block()
    require(block is not None, "width: no dense impact block was built")
    _dense_rows, impact = block
    sift, sift_seg, vecs = bench.make_sift_node(n, DIMS, args.seed)
    jax.block_until_ready(impact)
    out["segment_seconds"] = round(time.monotonic() - t0, 2)
    out["impact_block"] = [int(x) for x in impact.shape]
    log(f"width: segment + slab on device in {out['segment_seconds']}s, "
        f"impact {out['impact_block']}")

    servers = [RestServer(node, port=free_port()),
               RestServer(sift, port=free_port())]
    for s in servers:
        s.start(background=True)
    text_http = Http(servers[0].port, 600.0)
    knn_http = Http(servers[1].port, 600.0)

    def ref_bm25(terms):
        scores = np.zeros(n, np.float64)
        for t in terms:
            s, e = int(offsets[t]), int(offsets[t + 1])
            scores[u_doc[s:e]] += float(idf[t]) * tfn[s:e]
        return scores

    everything = np.ones(n, bool)
    queries = bench.make_queries(256, VOCAB, df, args.seed)
    t0 = time.monotonic()
    eq = []
    for i, q in enumerate(queries[:4]):
        r = text_http.ok("POST", "/msmarco/_search", {
            "query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
            "size": TOP_K, "_source": False})
        sc = ref_bm25(q)
        eq.append(check_topk(f"width match[{i}]", r["hits"]["hits"], sc,
                             sc > 0, TOP_K, RTOL_SINGLE))
    lines = []
    for q in queries:
        lines.append(json.dumps({"index": "msmarco"}))
        lines.append(json.dumps({
            "query": {"match": {"body": " ".join(f"t{t}" for t in q)}},
            "size": TOP_K, "_source": False}))
    r = text_http.ok("POST", "/_msearch", "\n".join(lines) + "\n")
    require(len(r["responses"]) == 256, "width _msearch: wrong count")
    sample = list(range(0, 256, 16))
    for i in sample:
        resp = r["responses"][i]
        require("error" not in resp, f"width _msearch[{i}]: {resp}")
        sc = ref_bm25(queries[i])
        check_topk(f"width _msearch[{i}]", resp["hits"]["hits"], sc,
                   sc > 0, TOP_K, RTOL_BATCHED)
    out["match"] = {"single": len(eq), "ids_equal": sum(eq),
                    "msearch": 256, "msearch_checked": len(sample),
                    "seconds": round(time.monotonic() - t0, 2)}
    log(f"width: match ok {out['match']}")

    rng = np.random.default_rng(args.seed + 3)
    qidx = rng.integers(0, n, 16)
    qvecs = (vecs[qidx] + 0.1 * rng.standard_normal(
        (16, DIMS))).astype(np.float32)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)

    def ref_knn(q):
        return (1.0 + (vn @ (q / np.linalg.norm(q))).astype(np.float64)
                ) * 0.5

    t0 = time.monotonic()
    for i, nc in enumerate((None, 64)):
        knn = {"field": "emb", "query_vector": qvecs[i].tolist(),
               "k": TOP_K, "ann": False}
        if nc:
            knn["num_candidates"] = nc
        r = knn_http.ok("POST", "/sift/_search", {
            "query": {"knn": knn}, "size": TOP_K, "_source": False})
        check_topk(f"width knn[{i}]", r["hits"]["hits"], ref_knn(qvecs[i]),
                   everything, TOP_K, RTOL_SINGLE)
    lines = []
    for q in qvecs:
        lines.append(json.dumps({"index": "sift"}))
        lines.append(json.dumps({"query": {"knn": {
            "field": "emb", "query_vector": q.tolist(), "k": TOP_K,
            "ann": False}}, "size": TOP_K, "_source": False}))
    r = knn_http.ok("POST", "/_msearch", "\n".join(lines) + "\n")
    for i, resp in enumerate(r["responses"]):
        require("error" not in resp, f"width knn _msearch[{i}]: {resp}")
        check_topk(f"width knn _msearch[{i}]", resp["hits"]["hits"],
                   ref_knn(qvecs[i]), everything, TOP_K, RTOL_BATCHED)
    out["knn"] = {"single": 2, "batched": 16,
                  "seconds": round(time.monotonic() - t0, 2)}
    log(f"width: knn ok {out['knn']}")
    for s in servers:
        s.stop()

    # -- the three kernels, at the dispatcher's tiles, against their twins --
    kern: dict = {}

    from elasticsearch_tpu.ops.knn import knn_topk_stored

    slab = sift_seg.vectors["emb"].vecs
    slab_terms = sift_seg.vectors["emb"].row_terms()
    Dk = int(slab.shape[0])
    klive = jnp.asarray(np.arange(Dk) < n)
    for Q, k, precise in ((8, 64, True), (256, 40, False)):
        tile = pk._knn_tile_for(Q, DIMS, k, Dk)
        require(tile and Dk >= 2 * tile, f"knn kernel gates out at Q={Q}")
        qs = jnp.asarray(np.resize(qvecs, (Q, DIMS)))
        t0 = time.monotonic()
        pv, pi = pk.knn_topk_pallas(qs, slab, klive, k=k, metric="cosine",
                                    tile=tile, interpret=interpret,
                                    precise=precise)
        pv, pi = np.asarray(pv), np.asarray(pi)
        secs = time.monotonic() - t0
        xv, xi = knn_topk_stored(qs, slab, slab_terms, klive, k=k,
                                 metric="cosine", use_bf16=False)
        xv, xi = np.asarray(xv), np.asarray(xi)
        rtol = RTOL_SINGLE if precise else RTOL_BATCHED
        overlap = np.mean([len(set(a) & set(b)) / k
                           for a, b in zip(pi.tolist(), xi.tolist())])
        require(np.allclose(pv[:, 0], xv[:, 0], rtol=rtol)
                and overlap >= (0.999 if precise else 0.9),
                f"knn kernel disagrees with its XLA twin at Q={Q} "
                f"(overlap {overlap})")
        kern[f"knn_topk Q={Q} k={k}"] = {
            "tile": tile, "precise": precise,
            "first_call_seconds": round(secs, 2),
            "topk_overlap": round(float(overlap), 4)}

    from elasticsearch_tpu.ops.pq import adc_sum

    M, K, W, Tp = 32, 256, 32768, 32  # pq_layout(128), 1M-vector IVF probe
    codes = jnp.asarray(rng.integers(0, K, (W, M)).astype(np.int32))
    lut = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
    pk_tile = pk.adc_pallas_tile(W, M, K) if on_tpu else 1024
    require(pk_tile, "adc kernel gates out")
    t0 = time.monotonic()
    got = np.asarray(pk.adc_scores_pallas(codes, lut, tile=pk_tile,
                                          interpret=interpret))
    secs = time.monotonic() - t0
    err = twin_error(got, np.asarray(adc_sum(jnp, codes, lut)))
    log(f"width: adc kernel vs twin: {err:.2e}")
    require(err <= RTOL_SINGLE,
            f"adc kernel disagrees with its XLA twin ({err:.2e})")
    kern["adc_scores"] = {"tile": pk_tile, "W": W, "M": M, "K": K,
                          "first_call_seconds": round(secs, 2),
                          "twin_error": err}

    luts = jnp.asarray(rng.standard_normal((Tp, M, K)).astype(np.float32))
    Wm = 4096
    ms_tile = pk.maxsim_adc_tile(Wm, M, K, Tp) if on_tpu else 512
    require(ms_tile, "maxsim kernel gates out")
    t0 = time.monotonic()
    got = np.asarray(pk.maxsim_adc_pallas(
        codes[:Wm], jnp.transpose(luts, (1, 2, 0)), t_real=Tp,
        tile=ms_tile, interpret=interpret))
    secs = time.monotonic() - t0
    err = twin_error(got, np.asarray(pk._maxsim_adc_xla(codes[:Wm], luts)))
    log(f"width: maxsim kernel vs twin: {err:.2e}")
    require(err <= RTOL_SINGLE,
            f"maxsim kernel disagrees with its XLA twin ({err:.2e})")
    kern["maxsim_adc"] = {"tile": ms_tile, "W": Wm, "M": M, "K": K,
                          "Tp": Tp, "first_call_seconds": round(secs, 2),
                          "twin_error": err}
    out["kernels"] = kern
    out["interpreted"] = interpret
    snap = kernels.snapshot()
    out["dispatch"] = {k: int(v) for k, v in sorted(snap.items())}
    for key in ZERO_KERNELS:
        require(snap.get(key, 0) == 0, f"width: {key} = {snap.get(key)}")
    stats = jax.devices()[0].memory_stats() or {}
    out["hbm"] = {"bytes_in_use": stats.get("bytes_in_use", 0),
                  "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
                  "bytes_limit": stats.get("bytes_limit", 0)}
    node.close()
    sift.close()
    print("WIDTH_RESULT " + json.dumps(out), flush=True)
    return 0


def twin_error(got: np.ndarray, want: np.ndarray) -> float:
    """Largest difference, relative to the largest reference magnitude
    (the unit tests hold these two kernels to rtol 1e-5 interpreted)."""
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def corpus_child(args) -> int:
    """numpy only: fill bench.py's corpus cache while the server ingests,
    so the width child loads it instead of building it on the chip's
    clock."""
    sys.path.insert(0, HERE)
    import bench

    bench.build_corpus(args.width_docs, VOCAB, args.seed)
    return 0


def width_phase(args, env, out_dir, corpus_proc, result):
    if corpus_proc is not None:
        rc = corpus_proc.wait(timeout=900)
        require(rc == 0, f"corpus builder exited with code {rc}:\n"
                         f"{tail(os.path.join(out_dir, 'corpus.log'))}")
    log_path = os.path.join(out_dir, "width.log")
    argv = [sys.executable, os.path.abspath(__file__), "--_width-child",
            "--width-docs", str(args.width_docs), "--seed", str(args.seed)]
    if args.rehearse_cpu:
        argv.append("--rehearse-cpu")
    p = spawn(argv, log_path, env)
    rc = p.wait(timeout=args.deadline)
    require(rc == 0, f"width child exited with code {rc}:\n{tail(log_path)}")
    with open(log_path, encoding="utf-8", errors="replace") as f:
        lines = [ln for ln in f if ln.startswith("WIDTH_RESULT ")]
    require(lines, f"width child printed no result:\n{tail(log_path)}")
    width = json.loads(lines[-1][len("WIDTH_RESULT "):])
    require(width["device"] == result["device"],
            f"width child saw {width['device']}, the server "
            f"{result['device']}")
    result["width"] = width


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=262_144,
                    help="documents loaded through _bulk (the product "
                         f"size is {FULL_DOCS}; the cut is printed as "
                         "`reduced`)")
    ap.add_argument("--width-docs", type=int, default=FULL_DOCS)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="CPU rehearsal: children run under "
                         "JAX_PLATFORMS=cpu, kernels interpreted, output "
                         "labelled platform: cpu. Never the default.")
    ap.add_argument("--skip-width", action="store_true",
                    help="serve phase only (debugging)")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="logs and result.json land here")
    ap.add_argument("--work", default=os.path.join(HERE, ".chip_smoke"),
                    help="data path of the servers (removed at the end)")
    ap.add_argument("--deadline", type=float, default=1170.0,
                    help="the run fails if it is not done by then")
    ap.add_argument("--_width-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--_corpus-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args._corpus_child:
        return corpus_child(args)
    if args._width_child:
        try:
            return width_child(args)
        except SmokeFailure as e:
            print(f"width child FAILED: {e}", file=sys.stderr)
            return 1

    out_dir, work_dir = os.path.abspath(args.out), os.path.abspath(args.work)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    env = dict(os.environ)
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    result: dict = {"ok": False, "device": None}
    watchdog = threading.Timer(args.deadline, _deadline, (args.deadline,))
    watchdog.daemon = True
    watchdog.start()
    try:
        width_wanted = not args.skip_width and args.docs < FULL_DOCS
        corpus_proc = None
        if width_wanted:
            corpus_proc = spawn(
                [sys.executable, os.path.abspath(__file__),
                 "--_corpus-child", "--width-docs", str(args.width_docs),
                 "--seed", str(args.seed)],
                os.path.join(out_dir, "corpus.log"), env)
        serve_phase(args, env, out_dir, work_dir, result)
        judge_serve(result)
        if width_wanted:
            width_phase(args, env, out_dir, corpus_proc, result)
        result["sizes"] = {
            "docs": args.docs, "vocab": VOCAB, "dims": DIMS,
            "shards": result["device"]["count"],
            "width_docs": args.width_docs if width_wanted else None}
        result["reduced"] = (
            [] if args.docs >= FULL_DOCS else
            [f"_bulk-loaded documents {args.docs} of {FULL_DOCS}: host "
             f"ingest and freeze time inside the run's limit"
             + (f"; width phase covers {args.width_docs} through the "
                f"segment loader" if width_wanted else
                "; width phase skipped")])
        result["seconds"] = round(time.monotonic() - T0, 1)
        result["ok"] = True
    except SmokeFailure as e:
        result["error"] = str(e)
        log(f"FAILED: {e}")
    except Exception as e:
        result["error"] = f"{type(e).__name__}: {e}"
        import traceback

        traceback.print_exc()
    finally:
        watchdog.cancel()
        stop_all()
        shutil.rmtree(work_dir, ignore_errors=True)
        result["claim"] = None
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(result, f, indent=1)
    if not result["ok"]:
        return 1
    print("SMOKE_REPORT " + json.dumps(result), flush=True)
    # the contract's last line: these two keys and no other
    print(json.dumps({"ok": True, "device": result["device"]}), flush=True)
    return 0


def _deadline(seconds: float):
    print(f"[smoke] FAILED: not done after {seconds:.0f}s", file=sys.stderr,
          flush=True)
    stop_all()
    os._exit(1)


if __name__ == "__main__":
    sys.exit(main())
