"""Process-level JAX set-up shared by every launcher.

:func:`enable_compilation_cache` decides where JAX's persistent
compilation cache lives: wherever ``JAX_COMPILATION_CACHE_DIR`` says when
the environment sets it (this module then sets no directory at all),
otherwise :data:`COMPILATION_CACHE_DIR`, one fixed git-ignored path
inside the checkout. The path is part of the cache key, so a directory
that moves between runs never hits.

:func:`host_fingerprint` is the host-machine identity digest that keys
CPU-compiled AOT blobs (parallel/aot.py): an XLA:CPU executable encodes
the exact host ISA features it was compiled for, so reloading it on a
different machine risks SIGILL. Keying the blob by the fingerprint turns
a cross-machine reload into a clean cache miss instead of a crash.
"""
from __future__ import annotations

import hashlib
import os
import threading

#: the one in-code cache location: <checkout>/.jax_cache (git-ignored)
COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_HOST_FP_LOCK = threading.Lock()
_HOST_FP: str = ""


def host_fingerprint() -> str:
    """12-hex digest of this host machine's CPU identity. Sources, in
    order of specificity: /proc/cpuinfo's model name + feature flags
    (Linux — the flags line is exactly the ISA-feature set XLA:CPU AOT
    results depend on), falling back to the platform module's
    machine/processor/platform tuple. Deterministic per machine, cached
    after first resolution, never raises."""
    global _HOST_FP
    if _HOST_FP:
        return _HOST_FP
    with _HOST_FP_LOCK:
        if _HOST_FP:
            return _HOST_FP
        parts = []
        try:
            with open("/proc/cpuinfo") as fh:
                seen = set()
                for line in fh:
                    key = line.split(":", 1)[0].strip()
                    if key in ("model name", "flags", "Features") \
                            and key not in seen:
                        seen.add(key)
                        parts.append(line.strip())
                    if len(seen) == 2:
                        break
        except OSError:
            pass
        if not parts:
            import platform as _platform

            parts = [_platform.machine(), _platform.processor(),
                     _platform.platform()]
        _HOST_FP = hashlib.sha1(
            "|".join(parts).encode("utf-8", "replace")).hexdigest()[:12]
        return _HOST_FP


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the directory from
    the environment itself and no directory is set here. Every compile is
    cached regardless of size or compile time: the per-query program zoo
    is wide (pow2 shape buckets x query kinds) but each entry is small."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILATION_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
