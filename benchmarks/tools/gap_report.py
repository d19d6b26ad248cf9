"""Where does the device's idle time go, and whose are the device's ops?

One set-up and one traced window of a cell, through ``run.py``'s own
functions (``loaders.load``, ``warm_up``, ``measure``), with the trace
kept; then ``trace/host_spans.py`` over it. Prints four tables:

1. the phase table — for every span name the program's tracer recorded
   over the window: ms a search answered (wall, self) and the spans a
   search, read from ``estpu_span_*`` like the ``span_ms.*`` metrics, and
   the self CPU a search over the traced interval alone (a phase reads
   the CPU clock only while the profiler session is on); under it, how
   the leaves and the containers' self time add up to the request's
   root span;
2. idle seconds of the traced interval by phase (``idle_by_phase``);
3. device seconds by XLA module, and the top device ops with the
   modules they ran in;
4. the device ops as a traced run's last line prints them (``breakdown``:
   ``host_spans.report`` is the one function this tool and ``run.py``
   read).

A diagnostic for the builder: it prints no last line, compares nothing
with the reference and is no part of the check.

    python3 benchmarks/tools/gap_report.py --workload <cell> --seed <n> [--out <dir>]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

FAMILIES = {"wall": "estpu_span_duration_seconds_sum",
            "n": "estpu_span_duration_seconds_count",
            "self": "estpu_span_self_seconds_total",
            "cpu": "estpu_span_cpu_seconds_total",
            "errors": "estpu_span_errors_total"}


def phase_table(pair, answered: int) -> dict:
    """{span name: {"ms", "self_ms", "cpu_ms", "spans", "errors"}}: a
    search answered (errors: over the window), from the counters before
    and after the window."""
    from benchmarks.metrics import counters

    names = set()
    for snap in pair:
        for labels in snap.get(FAMILIES["n"], {}):
            if labels.startswith('span="'):
                names.add(labels[len('span="'):-1])
    out = {}
    for name in sorted(names):
        rise = {k: counters.delta(pair, [{"family": fam,
                                           "labels": {"span": name}}])
                for k, fam in FAMILIES.items()}
        if rise["n"]:
            out[name] = {"ms": 1e3 * rise["wall"] / answered,
                         "self_ms": 1e3 * rise["self"] / answered,
                         "cpu_ms": 1e3 * rise["cpu"] / answered,
                         "spans": rise["n"] / answered,
                         "errors": rise["errors"]}
    return out


def sums(table: dict, names: dict) -> dict:
    """How the phase table adds up. Every leaf below a ``rest.request`` or
    a ``serving.batch`` root is counted once, so leaves + the containers'
    self time = the two roots' wall time; a parked request's
    ``serving.batch_wait`` spans the batch's execution a second time."""
    def ms(name, key="ms"):
        return table.get(name, {}).get(key, 0.0)

    leaves = sum(ms(n) for n in list(names["leaves"]) + list(names["derived"]))
    unaccounted = sum(ms(n, "self_ms") for n in names["containers"])
    return {"leaves_ms": leaves, "containers_self_ms": unaccounted,
            "request_ms": ms(names["root"]),
            "batch_ms": ms("serving.batch"),
            "leaves_plus_self_over_roots": (
                (leaves + unaccounted)
                / max(ms(names["root"]) + ms("serving.batch"), 1e-12))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None,
                    help="directory for report.json and the trace")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmarks import contract, loaders
    from benchmarks import run as bench
    from benchmarks.metrics import counters as counters_mod
    from benchmarks.trace import host_spans
    from benchmarks.trace import reduce as trace_reduce

    table = contract.load_table()
    files = bench.CellFiles(table, args.workload, args.rehearse)
    for key, value in files.config.get("environment", {}).items():
        os.environ[key] = str(value)
    devices = bench.device_or_exit(files.cell["chips"], args.rehearse)
    seconds = float(args.seconds or (2.0 if args.rehearse
                                     else table["run_seconds"]))
    rate = files.own.get("rate_qps")
    out_dir = args.out or os.path.join(
        bench.HERE, "out", args.workload, f"gap-{args.seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_dir = os.path.join(out_dir, "trace")

    from elasticsearch_tpu.rest.server import RestServer

    loaded = loaders.load(files.config, args.seed, devices, args.rehearse)
    server = RestServer(loaded.node, port=0)
    server.start(background=True)
    gen = bench.LoadGen(server.port, out_dir)

    def snapshot() -> dict:
        return counters_mod.parse(loaded.node.metrics.expose())

    def compiles() -> float:
        return counters_mod.total(
            snapshot(), [{"family": "estpu_program_compiles_total"}])

    try:
        bench.warm_up(files, gen, loaded, args.seed, seconds, rate, compiles)
        win = bench.measure(files, gen, loaded, args.seed, seconds, rate,
                            trace_dir, snapshot)
    finally:
        gen.close()
        server.stop()
    loaded.node.close()

    obs = bench.observed(win)
    names = host_spans.load_names()
    found = trace_reduce.find_trace(trace_dir)
    planes, window = trace_reduce.read_traced(found, args.rehearse)
    rep = host_spans.report(planes, window,
                            host_spans.read_host_events(found), names)
    phases = phase_table(win["counters"], max(obs["answered"], 1))
    tr = win["traced"]
    in_trace = sum(a is not None for r in win["requests"]
                   if r["done"] is not None
                   and tr["t_a"] <= r["done"] <= tr["t_b"]
                   for a in r["answers"])
    traced = phase_table(tr["counters"], max(in_trace, 1))
    for name, row in phases.items():
        row["traced_cpu_ms"] = traced.get(name, {}).get("cpu_ms", 0.0)
    rep.update({"workload": args.workload, "seed": args.seed,
                "seconds": seconds, "rate_qps": rate,
                "platform": devices[0].platform,
                "answered": obs["answered"],
                "answered_in_trace": in_trace,
                "end_to_end": bench.end_to_end(win),
                "host_cpu_ms": obs.get("host_cpu_ms"),
                "busy_s": trace_reduce.reduce_events(planes, window)[
                    "busy_s"],
                "phases": phases, "sums": sums(phases, names)})
    with open(os.path.join(out_dir, "report.json"), "w") as fh:
        json.dump(rep, fh, indent=1)

    print(f"gap report: {args.workload} seed {args.seed} on "
          f"[{rep['platform']}], {rep['answered']} searches answered in "
          f"{seconds:g} s; {rep['end_to_end']}; host_cpu_ms "
          f"{rep['host_cpu_ms']}")
    print("\n1. phases, ms a search answered over the window (self cpu: "
          f"over the traced interval, {in_trace} searches)")
    print(f"   {'span':26s}{'wall':>10s}{'self':>10s}{'self cpu':>10s}"
          f"{'spans':>9s}{'errors':>8s}")
    for name, row in sorted(phases.items(), key=lambda kv: -kv[1]["ms"]):
        print(f"   {name:26s}{row['ms']:10.4f}{row['self_ms']:10.4f}"
              f"{row['traced_cpu_ms']:10.4f}{row['spans']:9.4f}"
              f"{row['errors']:8.0f}")
    print(f"   {json.dumps(rep['sums'])}")
    print(f"\n2. idle seconds by phase: traced {rep['window_s']:.3f} s, "
          f"busy {rep['busy_s']:.4f} s, idle {rep['idle_s']:.4f} s in "
          f"{rep['idle_intervals']} intervals, {rep['span_events']} span "
          f"events; named share {rep['idle_named_share']:.3f}")
    for name, secs in rep["idle_by_phase"]:
        print(f"   {name:36s}{secs:10.4f}")
    print("\n3. device seconds by XLA module")
    for name, secs in rep["device_by_module"][:20]:
        print(f"   {secs:10.4f}  {name[:100]}")
    print("   top device ops and the modules they ran in")
    for name, secs, by in rep["ops_in_modules"]:
        mods = ", ".join(f"{m[:48] or '(none)'} {s:.4f}"
                         for m, s in list(by.items())[:3])
        print(f"   {secs:10.4f}  {name[:64]}  <- {mods}")
    print("\n4. the rows a traced run's last line carries (breakdown): "
          "device ops by printed name")
    for name, secs in rep["breakdown"]["device_ops"]:
        print(f"   {secs:10.4f}  {name}")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the server's pool must not hold the exit
    os._exit(code)
