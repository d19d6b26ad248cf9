"""Compile-cache counters: the honest ledger of the AOT executable cache.

The AOT layer (parallel/aot.py) resolves every executor device program
through a three-step lookup — in-process memo → serialized-executable
blob deserialize → fresh XLA compile — and each resolution must be
attributable, or "zero-warmup" becomes an unverifiable claim. This
module is the process-global counter store those resolutions record
into, kept OUTSIDE parallel/ so monitor/metrics.py::process_counters and
the per-node ``estpu_compile_cache_*`` collectors can read it without
importing the jit-binding packages (importing parallel/ pulls jax — a
metrics scrape on a jax-less embedder must stay cheap and safe).

Event names (the ``source`` label of ``estpu_compile_cache_events_total``):

  aot_hit          executable deserialized from the blob cache — no trace,
                   no XLA compile, the zero-warmup path
  xla_dir_hit      fresh lower+compile whose XLA work was served by the
                   persistent compilation-cache directory (jax's own
                   ``/jax/compilation_cache/cache_hits`` event fired
                   during THIS thread's compile)
  fresh            full price paid: traced + XLA-compiled from nothing
  corrupt_miss     blob failed its digest/unpickle — deleted, detected miss
  mismatch_miss    blob was valid but for another backend/jax version/host
                   — deleted, detected miss
  deserialize_error  a structurally-valid blob failed deserialize_and_load
                   — deleted, fell through to fresh compile
  store            serialized executable persisted to the blob tier
  store_skipped    dir-served compile NOT serialized on purpose — an
                   XLA-dir-loaded executable lacks the object code
                   serialize_executable needs and its blob would fail
                   deserialize ("Symbols not found") in every later
                   process; the dir cache already covers this machine
  store_error      serialization/persist failed (cache stays cold, the
                   compiled program still serves)
  call_fallback    a resolved executable rejected its arguments at call
                   time — dropped from the memo, the plain jit path served
  resolve_error    blob load or fresh AOT compile raised — logged, and the
                   plain jit path serves that shape class (re-raising the
                   program's own error if that is what it was)

Phase seconds (``estpu_compile_cache_seconds_total``): ``deserialize``,
``compile``, ``serialize``.

Availability: ``enabled_state()`` is None until the AOT layer first
resolves whether it is enabled — process_counters maps that to the -1
unknown sentinel so bench deltas render ``null`` (the jit_compiles
discipline: unavailable never mixes into arithmetic as a fake 0).
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

EVENTS = ("aot_hit", "xla_dir_hit", "fresh", "corrupt_miss",
          "mismatch_miss", "deserialize_error", "store", "store_skipped",
          "store_error", "call_fallback", "resolve_error")
PHASES = ("deserialize", "compile", "serialize")

_LOCK = threading.Lock()
_EVENTS: Dict[str, int] = {}
_SECONDS: Dict[str, float] = {}
#: None = the AOT layer never ran (unknown); True/False once resolved
_ENABLED: Optional[bool] = None


def note_enabled(flag: bool) -> None:
    global _ENABLED
    with _LOCK:
        _ENABLED = bool(flag)


def enabled_state() -> Optional[bool]:
    with _LOCK:
        return _ENABLED


def event(name: str, n: int = 1) -> None:
    with _LOCK:
        _EVENTS[name] = _EVENTS.get(name, 0) + n


def seconds(phase: str, s: float) -> None:
    with _LOCK:
        _SECONDS[phase] = _SECONDS.get(phase, 0.0) + float(s)


def events_snapshot() -> Dict[str, int]:
    """Every event name, zero-filled — collectors need the stable label
    set, not just the names that happened to fire."""
    with _LOCK:
        return {name: _EVENTS.get(name, 0) for name in EVENTS}


def seconds_snapshot() -> Dict[str, float]:
    with _LOCK:
        return {p: _SECONDS.get(p, 0.0) for p in PHASES}


def counter_values() -> Dict[str, float]:
    """Flat ``compile_cache.*`` keys for process_counters / bench deltas.
    While the AOT layer has never resolved (enabled_state() is None)
    every value is the -1 unknown sentinel, which counters_delta renders
    as a typed null — never a fake 0."""
    with _LOCK:
        unknown = _ENABLED is None
        out: Dict[str, float] = {}
        for name in EVENTS:
            out[f"compile_cache.{name}"] = \
                -1.0 if unknown else float(_EVENTS.get(name, 0))
        for p in PHASES:
            out[f"compile_cache.{p}_seconds"] = \
                -1.0 if unknown else round(_SECONDS.get(p, 0.0), 6)
        return out


def reset() -> None:
    """Test isolation only."""
    global _ENABLED
    with _LOCK:
        _EVENTS.clear()
        _SECONDS.clear()
        _ENABLED = None
