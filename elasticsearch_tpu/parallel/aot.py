"""AOT executable cache: serialized compiled programs beside the IVF blobs.

Every executor program is memoized per process but recompiled per
restart — a rolling restart, relocation, or scale-out serves its first
minutes at compile-bound latency (ROADMAP #6's warmup cliff). The pow2
padding discipline bounds the program universe, so the fix is mechanical:
persist the compiled executables themselves.

:func:`wrap` interposes on the executor's program factories
(parallel/executor.py): the jitted callable each factory builds is kept
as the always-correct fallback, and the first call at each concrete
arg-shape class resolves a ``jax.stages.Compiled`` through a three-step
lookup —

1. **memo** — this process already resolved the (program, arg-sig) pair;
2. **blob deserialize** — ``jax.experimental.serialize_executable``
   round-trip through the content-addressed blob tier
   (index/ivf_cache.py ``load_blob``/``store_blob``, ``.aotx`` files in
   every registered data directory). No tracing, no XLA work: the
   zero-warmup path. A blob that fails its digest, carries another
   backend/jax-version/host fingerprint, or fails to load is DELETED and
   counted — a detected miss, never a crash or a silently wrong program;
3. **fresh compile** — ``jit(...).lower(*args).compile()`` (the
   ``Lowered`` AOT surface), then serialize + store so the NEXT process
   skips it. A compile whose XLA work was served by jax's persistent
   compilation-cache directory is counted ``xla_dir_hit`` (the
   ``/jax/compilation_cache/cache_hits`` monitoring event on this
   thread), distinct from a full-price ``fresh`` — the three sources
   stay separable in ``estpu_compile_cache_events_total``.

Key anatomy: ``sha1(program, factory-key digest, arg shape/dtype sig,
backend fingerprint, jax version, host fingerprint on CPU)``. The
factory-key digest makes two structurally different programs with
identical arg shapes (two compiled DSL trees) distinct; the backend and
jax-version components make a census captured on one chip generation or
jax build unreachable from another; the host fingerprint
(utils/platform.py) keeps XLA:CPU executables — which encode exact host
ISA features — machine-private (the SIGILL concern that used to disable
the CPU persistent cache entirely).

Failure discipline: a resolved executable that rejects its arguments at
call time (aval/sharding drift) falls back to the plain jitted callable
and latches that arg-sig off (``call_fallback``) — correctness never
depends on this cache. Accounting lands in monitor/compile_cache.py and,
per (program, shapes, backend) key, in the ProgramRegistry's
``cache_sources`` (the ``cache`` column of ``_cat/programs``).

Trace-audit interplay (the acceptance criterion's measurement): a fresh
compile traces the body, so the auditor counts it and the observatory
files the call as a compile; a deserialized executable never traces —
the first post-restart call records as a cached execute, searches label
``warmup=false``, and ``estpu_program_compiles_total`` stays flat.

Blob trust: the payload is a pickle (jax's own serialize_executable
format is pickle-based) read only from this node's registered data
directories — the same trust boundary as jax's persistent compilation
cache and every other blob in the tier.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional, Set, Tuple

VERSION = 2  # 2: payload names the devices the executable was compiled for
_EXT = "aotx"

_ENABLED_LOCK = threading.Lock()
_ENABLED: Optional[bool] = None


def _enabled() -> bool:
    """ESTPU_AOT_CACHE gate, resolved once (and reported to the counter
    store so 'never ran' stays distinguishable from 'ran, zero hits')."""
    global _ENABLED
    if _ENABLED is not None:
        return _ENABLED
    with _ENABLED_LOCK:
        if _ENABLED is None:
            flag = os.environ.get("ESTPU_AOT_CACHE", "1").lower() \
                not in ("0", "off", "false", "none")
            from elasticsearch_tpu.monitor import compile_cache

            compile_cache.note_enabled(flag)
            _ENABLED = flag
    return _ENABLED


def reset_enabled_for_tests() -> None:
    global _ENABLED
    with _ENABLED_LOCK:
        _ENABLED = None


# -- xla persistent-dir hit attribution --------------------------------------

_XLA_HITS = threading.local()
_LISTENER_LOCK = threading.Lock()
_LISTENER_INSTALLED = False


def _ensure_listener() -> None:
    """One process-wide monitoring listener: jax emits
    ``/jax/compilation_cache/cache_hits`` synchronously on the compiling
    thread, so a per-thread counter delta around lower+compile
    attributes the dir hit to exactly the program that got it."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return
        try:
            from jax._src import monitoring

            def _on_event(name: str, **_kw) -> None:
                if name == "/jax/compilation_cache/cache_hits":
                    _XLA_HITS.n = getattr(_XLA_HITS, "n", 0) + 1

            monitoring.register_event_listener(_on_event)
        except Exception:
            pass  # private surface: without it every compile is "fresh"
        _LISTENER_INSTALLED = True


def _xla_hits() -> int:
    return getattr(_XLA_HITS, "n", 0)


# -- key / frame --------------------------------------------------------------

def _host_component() -> str:
    """Host fingerprint on CPU backends (XLA:CPU executables are
    host-ISA-specific); empty elsewhere — a TPU executable is portable
    across hosts driving the same chip generation."""
    from elasticsearch_tpu.monitor.programs import backend_fingerprint
    from elasticsearch_tpu.utils.platform import host_fingerprint

    fp = backend_fingerprint()
    return host_fingerprint() if fp.startswith("cpu") else ""


def blob_key(program: str, key_digest: str, sig: str) -> str:
    from elasticsearch_tpu.monitor.programs import backend_fingerprint

    import jax

    ident = repr(("aotx", VERSION, program, key_digest, sig,
                  backend_fingerprint(), jax.__version__,
                  _host_component()))
    return "aot_" + hashlib.sha1(ident.encode("utf-8")).hexdigest()


def _frame(payload: dict) -> bytes:
    body = pickle.dumps(payload)
    return hashlib.sha1(body).hexdigest().encode("ascii") + b"\n" + body


def _unframe(blob: bytes) -> Optional[dict]:
    try:
        digest, _, body = blob.partition(b"\n")
        if hashlib.sha1(body).hexdigest().encode("ascii") != digest:
            return None
        payload = pickle.loads(body)
        return payload if isinstance(payload, dict) else None
    except Exception:
        return None


# -- the wrapper --------------------------------------------------------------

class AotProgram:
    """Callable façade over one factory-built jitted program: per
    arg-shape-class resolution memo → blob → fresh, with the jitted
    callable as the unconditional correctness fallback."""

    __slots__ = ("_fn", "program", "_key_digest", "_lock", "_memo",
                 "_failed")

    def __init__(self, fn: Any, program: str, key_digest: str):
        self._fn = fn
        self.program = program
        self._key_digest = key_digest
        self._lock = threading.Lock()
        self._memo: Dict[str, Any] = {}
        self._failed: Set[str] = set()

    # expose the jitted surface tests/tools poke at
    @property
    def jitted(self):
        return self._fn

    def __call__(self, *args, **kw):
        # kw: STATIC keyword arguments only (static_argnames of the
        # wrapped jit — ints/strings/bools). They join the arg sig (the
        # memo/blob key) and are baked at lowering time, so the
        # Compiled executable is invoked with the dynamic args alone.
        from elasticsearch_tpu.monitor.programs import shape_sig

        sig = shape_sig(args, kw) if kw else shape_sig(args)
        with self._lock:
            compiled = self._memo.get(sig)
        if compiled is None:
            compiled = self._resolve(sig, args, kw)
        if compiled is None:
            return self._fn(*args, **kw)
        try:
            return compiled(*args)
        except (TypeError, ValueError):
            # ARGUMENT-BINDING failure (aval/weak-type/layout drift the
            # serialized executable didn't expect — raised before any
            # device work): latch this shape class onto the plain jit
            # path and delete the blob. self._failed is per-process,
            # and a drifted blob left on disk would make EVERY restart
            # pay deserialize + failed call + full recompile while
            # counting a fake aot_hit. Any OTHER exception (an
            # XlaRuntimeError from the program itself) propagates
            # untouched: the program would fail identically under plain
            # jit, the caller's own failure handling (the executor's
            # force_scatter insurance) owns it, and re-running it here
            # would pay a doomed second compile and destroy a blob that
            # is not corrupt.
            from elasticsearch_tpu.monitor import compile_cache

            compile_cache.event("call_fallback")
            with self._lock:
                self._memo.pop(sig, None)
                self._failed.add(sig)
            try:
                from elasticsearch_tpu.index import ivf_cache

                ivf_cache.delete_blob(
                    blob_key(self.program, self._key_digest, sig), _EXT)
            except Exception:
                pass  # best-effort: the latch already protects this run
            return self._fn(*args, **kw)

    # -- resolution ----------------------------------------------------------

    def _resolve(self, sig: str, args: tuple, kw: Optional[dict] = None):
        if not _enabled():
            return None
        with self._lock:
            if sig in self._memo:
                return self._memo[sig]
            if sig in self._failed:
                return None
        # resolve OUTSIDE the lock (the executor _cached_data rule: a
        # duplicate build is wasted work, a serialized compile is a
        # stall) — a warmup thread compiling a NEW shape class of this
        # program must not block foreground calls on already-warm sigs
        # at the memo read above; two threads racing the SAME new sig
        # both pay, and the second publish wins harmlessly
        try:
            key = blob_key(self.program, self._key_digest, sig)
            compiled = self._load(key, args)
            if compiled is None:
                compiled = self._compile_and_store(key, sig, args, kw)
        except Exception:
            # the plain jit path serves this shape class from now on (and
            # re-raises whatever is wrong with the program itself) — but
            # never unseen: counted, and logged with its traceback
            import logging

            from elasticsearch_tpu.monitor import compile_cache

            compile_cache.event("resolve_error")
            logging.getLogger(__name__).exception(
                "AOT resolution of [%s] failed; serving it through plain "
                "jit", self.program)
            compiled = None
        with self._lock:
            if compiled is not None:
                self._memo[sig] = compiled
            else:
                self._failed.add(sig)
        return compiled

    def _load(self, key: str, args: tuple):
        """Blob → Compiled, with every failure a counted, deleted miss."""
        from elasticsearch_tpu.index import ivf_cache
        from elasticsearch_tpu.monitor import compile_cache

        blob = ivf_cache.load_blob(key, _EXT)
        if blob is None:
            return None
        payload = _unframe(blob)
        if payload is None or payload.get("version") != VERSION \
                or "exe" not in payload:
            ivf_cache.delete_blob(key, _EXT)
            compile_cache.event("corrupt_miss")
            return None
        if not self._fingerprints_match(payload):
            # unreachable via the key construction (the fingerprints are
            # key components) but cheap defense against key collisions
            # and hand-moved blob files: stale is a DETECTED miss
            ivf_cache.delete_blob(key, _EXT)
            compile_cache.event("mismatch_miss")
            return None
        try:
            from jax.experimental import serialize_executable as se

            import jax

            # load onto the devices the executable was compiled for:
            # the default (every device of the backend) makes a
            # one-device program on a four-chip host demand 4 shards
            # of every argument at call time
            by_id = {d.id: d for d in jax.devices()}
            t0 = time.perf_counter()
            compiled = se.deserialize_and_load(
                payload["exe"], payload["in_tree"], payload["out_tree"],
                execution_devices=[by_id[i] for i in payload["devices"]])
            compile_cache.seconds("deserialize",
                                  time.perf_counter() - t0)
        except Exception:
            ivf_cache.delete_blob(key, _EXT)
            compile_cache.event("deserialize_error")
            return None
        compile_cache.event("aot_hit")
        self._note_source("aot_hit", args)
        return compiled

    @staticmethod
    def _fingerprints_match(payload: dict) -> bool:
        from elasticsearch_tpu.monitor.programs import backend_fingerprint

        import jax

        return (payload.get("backend") == backend_fingerprint()
                and payload.get("jax") == jax.__version__
                and payload.get("host") == _host_component())

    def _compile_and_store(self, key: str, sig: str, args: tuple,
                           kw: Optional[dict] = None):
        """Fresh AOT compile (classified fresh vs xla_dir_hit by the
        persistent-dir event delta), then best-effort serialize+store —
        a persistence failure costs the next process a compile, never
        this call its program."""
        from elasticsearch_tpu.monitor import compile_cache

        _ensure_listener()
        hits0 = _xla_hits()
        t0 = time.perf_counter()
        compiled = self._fn.lower(*args, **(kw or {})).compile()
        compile_cache.seconds("compile", time.perf_counter() - t0)
        source = "xla_dir_hit" if _xla_hits() > hits0 else "fresh"
        compile_cache.event(source)
        self._note_source(source, args)
        if source == "xla_dir_hit":
            # NEVER serialize a dir-served executable: XLA rebuilds it
            # without the object code serialize_executable needs, and
            # the resulting blob deserializes to "Symbols not found" in
            # the next process (observed on XLA:CPU; the detected-miss
            # machinery would then delete + re-store the same poison
            # every restart). The dir cache itself already covers this
            # machine's restarts for the program — skipping the store
            # costs nothing but the cross-directory redundancy.
            compile_cache.event("store_skipped")
            return compiled
        try:
            from jax.experimental import serialize_executable as se

            from elasticsearch_tpu.index import ivf_cache
            from elasticsearch_tpu.monitor.programs import \
                backend_fingerprint

            import jax

            t0 = time.perf_counter()
            exe, in_tree, out_tree = se.serialize(compiled)
            blob = _frame({
                "version": VERSION,
                "program": self.program,
                "sig": sig,
                "backend": backend_fingerprint(),
                "jax": jax.__version__,
                "host": _host_component(),
                # serialize() itself reads _unloaded_executable; its
                # device_list is the assignment order the load must keep
                "devices": [d.id for d in compiled._executable
                            ._unloaded_executable.device_list],
                "exe": exe,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            compile_cache.seconds("serialize", time.perf_counter() - t0)
            # overwrite=False: the key digests program structure + arg
            # sig + every fingerprint — identical key ⇒ equivalent
            # executable, so the content-addressed skip is safe here
            ivf_cache.store_blob(key, blob, _EXT, overwrite=False)
            compile_cache.event("store")
        except Exception:
            compile_cache.event("store_error")
        return compiled

    def _note_source(self, source: str, args: tuple) -> None:
        """Attribute the resolution to the observatory key of the
        dispatch wrapper currently timing this call (the contextvar
        REGISTRY.timed sets); standalone calls fall back to
        (factory name, raw arg sig)."""
        try:
            from elasticsearch_tpu.monitor import programs

            programs.REGISTRY.record_cache_source(
                source, fallback_program=self.program,
                fallback_shapes=programs.shape_sig(args))
        except Exception:
            pass  # accounting must never fail a resolution


def wrap(fn: Any, program: str, key: Tuple) -> Any:
    """Wrap a factory-built jitted program for AOT caching. ``key`` is
    the factory's own program-cache key — content-stable tuples of
    strings/ints (struct keys, static dims, kernel-config tuples), so
    its repr digest identifies the program STRUCTURE across processes
    the way the arg sig alone cannot (two DSL trees can share arg
    shapes). Returns ``fn`` unchanged when the cache is disabled."""
    if not _enabled():
        return fn
    digest = hashlib.sha1(repr(key).encode("utf-8")).hexdigest()[:16]
    return AotProgram(fn, program, digest)
