"""Content-addressed IVF blob cache: makes `store.read_ivf` a product input.

An IvfIndex's list entries are LOCAL doc ordinals padded with the owning
segment's ``max_docs`` sentinel, so a persisted blob is only valid for a
slab whose vectors sit at exactly the same ordinals. Rather than trying to
track segment identity across restarts / translog replays / snapshot
restores (where segment boundaries legitimately change — replay merges all
live docs into one segment), blobs are keyed by a digest of the exact slab
content: ``sha1(shape, metric, max_docs, vecs bytes, exists bytes)``. A key
hit therefore *guarantees* the ordinals line up and the blob can be loaded
verbatim; any content drift (deletes dropped on restore, different refresh
boundaries) simply misses and falls back to the k-means build, which then
re-persists under the new key.

Lifecycle (reference behavioral analogue: Lucene writes its HNSW/IVF graph
into segment files at flush and reopens it on restart —
org/elasticsearch/index/engine/InternalEngine.java's commit path; ES 2.0
itself has no vector format, this follows the north-star `dense_vector`
addition):

- `Node(data_path=...)` calls `register(<data>/_ivf)` before gateway
  recovery, so replayed segments can hit blobs written by the previous
  process; `Node.close()` unregisters it. Several Nodes in one process
  each register their own directory (refcounted — two Nodes over the
  same data_path share one registration).
- `VectorColumn.get_ivf` consults the cache before `build_ivf` and stores
  the blob after a build (counters: `ivf_cache_hit` / `ivf_build` in
  `monitor.kernels`, surfaced via `_nodes/stats`).
- Snapshots embed each segment's blobs; restore seeds them here so the
  target node's freeze skips the k-means when the restored slab content
  matches (single-segment shards with no pruned deletes).

The in-memory layer is content-addressed and process-global, which is safe
by construction even with several Nodes in one process: identical key ==
identical slab, so a blob can never be applied to the wrong data. The
durable tier is the union of the registered directories: loads scan all of
them, stores write to all of them. Writing a blob into a sibling node's
directory is additive cache pollution at worst (content addressing makes a
stale or foreign blob unreachable unless its exact slab recurs), and it is
what keeps every data-path node's cache warm across restarts regardless of
which node in the process built the quantizer.
"""
from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Dict, List, Optional

import numpy as np

from elasticsearch_tpu.monitor import kernels

_LOCK = threading.Lock()
_DIRS: Dict[str, int] = {}  # directory -> refcount (insertion-ordered)
_MEM: Dict[str, bytes] = {}
_MEM_CAP = 64  # blobs; FIFO eviction — disk layer is the durable tier


def register(directory: str) -> None:
    """Add ``directory`` to the durable tier (created on first store).
    Refcounted: a second Node over the same data_path shares it."""
    with _LOCK:
        _DIRS[directory] = _DIRS.get(directory, 0) + 1


def unregister(directory: str) -> None:
    """Drop one registration of ``directory`` (Node.close)."""
    with _LOCK:
        c = _DIRS.get(directory, 0) - 1
        if c > 0:
            _DIRS[directory] = c
        else:
            _DIRS.pop(directory, None)


def configure(directory: Optional[str]) -> None:
    """Back-compat shim: register(directory); None is a no-op."""
    if directory:
        register(directory)


def reset() -> None:
    """Drop all cache state (tests)."""
    with _LOCK:
        _DIRS.clear()
        _MEM.clear()


def content_key(vecs_host: np.ndarray, exists_host: np.ndarray,
                metric: str, max_docs: int) -> str:
    v = np.ascontiguousarray(vecs_host, dtype=np.float32)
    e = np.ascontiguousarray(exists_host, dtype=bool)
    h = hashlib.sha1()
    h.update(repr((v.shape, metric, int(max_docs))).encode())
    # the arrays' own buffers: no second host copy of a multi-GB slab
    h.update(memoryview(v).cast("B"))
    h.update(memoryview(e).cast("B"))
    return h.hexdigest()


def _disk_paths(key: str, ext: str = "ivf") -> List[str]:
    with _LOCK:
        dirs = list(_DIRS)
    return [os.path.join(d, f"{key}.{ext}") for d in dirs]


def load(key: str):
    """Return a device-resident IvfIndex for ``key`` or None. A corrupt
    disk blob is deleted and treated as a miss (the build path re-creates
    it), never propagated."""
    from elasticsearch_tpu.index.store import CorruptStoreException, read_ivf

    with _LOCK:
        blob = _MEM.get(key)
    if blob is not None:
        try:
            idx = read_ivf(blob)
        except CorruptStoreException:
            with _LOCK:
                _MEM.pop(key, None)
        else:
            kernels.record("ivf_cache_hit")
            return idx
    for path in _disk_paths(key):
        if not os.path.exists(path):
            continue
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
        except OSError:
            continue
        try:
            idx = read_ivf(blob)
        except CorruptStoreException:
            try:
                os.unlink(path)
            except OSError:
                pass
            continue
        kernels.record("ivf_cache_hit")
        return idx
    return None


def store(key: str, ivf: Any) -> bytes:
    """Persist ``ivf`` under ``key`` (memory + every registered directory).
    Returns the encoded blob (snapshot payloads reuse it)."""
    from elasticsearch_tpu.index.store import write_ivf

    blob = write_ivf(ivf)
    seed(key, blob)
    return blob


def load_pq(key: str):
    """Return host-side PqHostParts for ``key`` or None — the PQ sibling
    of :func:`load`, sharing the content-address (one slab digest keys
    both its IVF quantizer and its PQ codes, under different
    extensions). A corrupt blob is deleted and treated as a miss."""
    from elasticsearch_tpu.index.store import CorruptStoreException, read_pq

    mkey = f"pq:{key}"
    with _LOCK:
        blob = _MEM.get(mkey)
    if blob is not None:
        try:
            parts = read_pq(blob)
        except CorruptStoreException:
            with _LOCK:
                _MEM.pop(mkey, None)
        else:
            kernels.record("pq_cache_hit")
            return parts
    for path in _disk_paths(key, ext="pq"):
        if not os.path.exists(path):
            continue
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
        except OSError:
            continue
        try:
            parts = read_pq(blob)
        except CorruptStoreException:
            try:
                os.unlink(path)
            except OSError:
                pass
            continue
        kernels.record("pq_cache_hit")
        return parts
    return None


def store_pq(key: str, parts: Any) -> bytes:
    """Persist host PqHostParts under ``key`` (memory + every registered
    directory). Returns the encoded blob (snapshot payloads reuse it)."""
    from elasticsearch_tpu.index.store import write_pq

    blob = write_pq(parts)
    seed_pq(key, blob)
    return blob


def seed_pq(key: str, blob: bytes) -> None:
    """Insert an already-encoded PQ blob (snapshot restore pre-seeding)."""
    _seed(f"pq:{key}", blob, _disk_paths(key, ext="pq"))


def seed(key: str, blob: bytes) -> None:
    """Insert an already-encoded IVF blob (snapshot restore pre-seeding)."""
    _seed(key, blob, _disk_paths(key))


# -- generic blob tier (program-key census, incidents, ...) ------------------

def frame_blob(payload: dict) -> bytes:
    """The generic tier's one digest framing: ``sha1-hex\\n{json}``.
    Corruption (torn write, bitrot) becomes a *detected* miss at
    :func:`unframe_blob` — every JSON blob family (census, incidents)
    shares this frame so the format can't silently diverge."""
    import json

    body = json.dumps(payload, sort_keys=True, default=str).encode("utf-8")
    return hashlib.sha1(body).hexdigest().encode("ascii") + b"\n" + body


def unframe_blob(blob: bytes) -> Optional[dict]:
    """Verify + decode a :func:`frame_blob` payload; None on any
    corruption (callers treat it as a miss and usually delete_blob)."""
    import json

    try:
        digest, _, body = blob.partition(b"\n")
        if hashlib.sha1(body).hexdigest().encode("ascii") != digest:
            return None
        payload = json.loads(body)
        return payload if isinstance(payload, dict) else None
    except Exception:
        return None


def load_blob(key: str, ext: str) -> Optional[bytes]:
    """Raw blob bytes for ``(key, ext)`` from memory or any registered
    directory, or None. No decoding here — callers validate (and call
    :func:`delete_blob` on corruption, so a bad blob becomes a clean
    miss instead of a crash). Lets siblings of the IVF/PQ artifacts —
    the per-index program-key census (resources/census.py) — ride the
    same durable tier without duplicating the directory registry."""
    mkey = f"{ext}:{key}"
    with _LOCK:
        blob = _MEM.get(mkey)
    if blob is not None:
        return blob
    for path in _disk_paths(key, ext=ext):
        if not os.path.exists(path):
            continue
        try:
            with open(path, "rb") as fh:
                return fh.read()
        except OSError:
            continue
    return None


def store_blob(key: str, blob: bytes, ext: str,
               overwrite: bool = True) -> None:
    """Persist raw bytes under ``(key, ext)`` (memory + every registered
    directory; best-effort on disk like every other blob here).

    ``overwrite`` defaults True: unlike the content-addressed IVF/PQ
    blobs (identical key ⇒ identical bytes, so skip-if-exists is a pure
    optimization), the generic tier's families are NAME-addressed and
    MUTABLE — the census merges on every flush, the incident index
    appends — and a skip-if-exists store would silently freeze the disk
    copy at its first write (the in-memory tier masking it until the
    process dies). Content-addressed callers (AOT executables) pass
    ``overwrite=False`` to keep the cheap skip."""
    _seed(f"{ext}:{key}", blob, _disk_paths(key, ext=ext),
          overwrite=overwrite)


def delete_blob(key: str, ext: str) -> None:
    """Drop ``(key, ext)`` everywhere — the corrupt-blob miss path."""
    with _LOCK:
        _MEM.pop(f"{ext}:{key}", None)
    for path in _disk_paths(key, ext=ext):
        try:
            os.unlink(path)
        except OSError:
            pass


def list_blob_keys(ext: str) -> List[str]:
    """Every key currently stored under ``ext``: memory tier plus each
    registered directory. This is the delta input for fleet-wide AOT
    blob distribution — a recovery target sends the `.aotx` keys it
    already HAS in its shard_sync request, and the source ships only the
    complement, so a joining node never compiles a program any peer
    already compiled."""
    prefix = f"{ext}:"
    with _LOCK:
        keys = {k[len(prefix):] for k in _MEM if k.startswith(prefix)}
        dirs = list(_DIRS)
    suffix = f".{ext}"
    for d in dirs:
        try:
            names = os.listdir(d)
        except OSError:
            continue
        keys.update(n[:-len(suffix)] for n in names if n.endswith(suffix))
    return sorted(keys)


def _seed(mkey: str, blob: bytes, paths: List[str],
          overwrite: bool = False) -> None:
    with _LOCK:
        if mkey not in _MEM and len(_MEM) >= _MEM_CAP:
            _MEM.pop(next(iter(_MEM)))
        _MEM[mkey] = blob
    for path in paths:
        if not overwrite and os.path.exists(path):
            continue
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # tmp name unique per WRITER, not just per process: overwrite-
        # mode stores race across threads (watchdog flush vs recovery
        # flush vs close), and a shared tmp lets one writer publish
        # another's half-written bytes via os.replace — the digest frame
        # would then detect-and-DELETE the census on next load, losing
        # the exact durability the overwrite exists to provide
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
