"""What does one span cost? Opens and closes ``--n`` child spans under a
root, with the metrics sink the node installs, and prints the
microseconds one open-and-close takes: with no active span (the shared
no-op), with a root but no profiler session, and inside a
``jax.profiler`` session (Python tracer off, as ``run.py`` traces). A host
time of the machine it runs on; for the builder, no part of the check.

    python3 benchmarks/tools/span_cost.py [--n 200000]
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def loop(n: int) -> float:
    from elasticsearch_tpu.tracing import span

    t0 = time.perf_counter()
    for _ in range(n):
        with span("device.dispatch", program="x"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def primitives(n: int) -> None:
    """What a span is made of, each alone: us a call on this host."""
    import contextvars
    import threading

    import jax

    var = contextvars.ContextVar("x", default=None)
    lock = threading.Lock()

    def locked():
        with lock:
            pass

    def ctx():
        var.reset(var.set(1))

    def annotation():
        with jax.profiler.TraceAnnotation("x", t="0123456789abcdef"):
            pass

    for name, fn in (("time.perf_counter", time.perf_counter),
                     ("time.thread_time", time.thread_time),
                     ("time.time", time.time),
                     ("threading.get_ident", threading.get_ident),
                     ("lock acquire+release", locked),
                     ("contextvar set+reset", ctx),
                     ("TraceAnnotation enter+exit", annotation)):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        print(f"  {name}: {(time.perf_counter() - t0) / n * 1e6:.3f}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args()
    import jax

    from elasticsearch_tpu.monitor.metrics import MetricsRegistry, span_sink
    from elasticsearch_tpu.tracing import Tracer

    tracer = Tracer("cost")
    tracer.set_sink(span_sink(MetricsRegistry()))
    print(f"platform {jax.devices()[0].platform}; {args.n} spans a reading, "
          f"us one open-and-close")
    primitives(args.n)
    print(f"no active span (shared no-op): {loop(args.n):.3f}")
    for _ in range(3):
        with tracer.span("rest.request"):
            print(f"child span, no profiler session: {loop(args.n):.3f}")
    out = tempfile.mkdtemp(prefix="span_cost_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=options)
        try:
            with tracer.span("rest.request"):
                # a traced window of a cell holds ~5,000 span events
                print("child span, inside a profiler session: "
                      f"{loop(min(args.n, 20_000)):.3f}")
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            print(f"stop_trace took {time.perf_counter() - t0:.3f} s")
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
