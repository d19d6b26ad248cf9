"""One run of one cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip: it imports JAX, builds the index on the
device, serves it with the product's own ``RestServer`` on a free port of
127.0.0.1 and, in a traced run, takes the profiler trace. The load comes
from ``loadgen/client.py``, a child started as a script that imports
nothing of JAX or of the program. Standard output carries one line, the
last thing this process writes, and only after ``check_last_line`` has
passed it; everything else goes to standard error and to
``benchmarks/out/<cell>/<seed>.json``. No TPU, or fewer chips than the
cell asks for: exit 3 and nothing on standard output.

The benchmark's own flags: ``--rehearse`` (every cell's whole path at a
thousand documents on the CPU; prints no line and no time), ``--control 1``
(also read the configuration kind's control on the same sample; a
rehearsal with it fails where the control comes out correct), ``--sweep
r1,r2,..`` (one set-up, then the window at each rate; prints no line).
How a cell's files are found: benchmarks/README.md.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import contract  # noqa: E402

NO_CHIP = 3
TRACE_SECONDS = 3.0


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


class CellFiles:
    """A cell's files, found by the names in BENCHMARK.json."""

    def __init__(self, table: dict, workload: str, rehearse: bool):
        self.cell = contract.cell_of(table, workload)
        cfg = contract.config_of(table, self.cell["config"])
        with open(os.path.join(ROOT, cfg["file"])) as fh:
            self.config = json.load(fh)
        self.traffic = read_json("traffic", f"{self.cell['traffic']}.json")
        self.own = read_json("cells", f"{workload}.json")
        if rehearse:
            self.traffic.update(self.traffic.get("rehearsal", {}))
            self.own.update(self.own.get("rehearsal", {}))


class LoadGen:
    """The child that sends the load. One command a phase."""

    def __init__(self, port: int, work_dir: str):
        self.work_dir = work_dir
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen", "client.py"),
             str(port)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"})
        self.n = 0

    def start(self, schedule: dict) -> str:
        self.n += 1
        sched = os.path.join(self.work_dir, f"schedule-{self.n}.json")
        out = os.path.join(self.work_dir, f"result-{self.n}.json")
        with open(sched, "w") as fh:
            json.dump(schedule, fh)
        self.proc.stdin.write(json.dumps({"schedule": sched, "out": out})
                              + "\n")
        self.proc.stdin.flush()
        return out

    def wait(self, out: str) -> dict:
        line = self.proc.stdout.readline()
        if line.strip() != "done":
            raise RuntimeError(
                f"the load generator ended early (exit "
                f"{self.proc.poll()}): {line!r}")
        with open(out) as fh:
            return json.load(fh)

    def run(self, schedule: dict) -> dict:
        return self.wait(self.start(schedule))

    def close(self):
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"quit": true}\n')
                self.proc.stdin.flush()
                self.proc.stdin.close()
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()


def percentile(values: list, p: float) -> float:
    """Nearest-rank percentile of all values (inf where a reply is
    missing sorts last)."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, int(-(-p * len(xs) // 100)) - 1))]


# --------------------------------------------------------------------------
# what a phase's records say
# --------------------------------------------------------------------------

def unpack(schedule: dict, result: dict, loaded) -> list:
    """One entry a request: {"pool": [...], "due", "sent", "done",
    "answers": [answer | None, ...]}; what of a reply is its answer is the
    configuration kind's to say (``loaded.answer``), and an answer is None
    where the request got no valid reply."""
    out = []
    for idx, due, sent, done, status, text in result["records"]:
        req = (schedule["requests"][idx] if schedule["mode"] == "open"
               else schedule["requests"][idx[0]][idx[1]])
        answers = [None] * len(req["pool"])
        if status == 200:
            try:
                body = json.loads(text)
                replies = (body["responses"] if req["path"] == "/_msearch"
                           else [body])
                if len(replies) == len(answers):
                    answers = [loaded.answer(r) if isinstance(r, dict)
                               else None for r in replies]
            except (ValueError, KeyError, TypeError):
                pass
        out.append({"pool": req["pool"], "due": due, "sent": sent,
                    "done": done, "answers": answers,
                    "status": status,
                    "error": None if status == 200 else text[:300]})
    return out


def sample_answers(requests: list, loaded, n: int, seed: int) -> list:
    """(pool index, answer) pairs to hold to the reference: a seeded sample
    of the searches that got a reply, the one with the most work in it."""
    import numpy as np

    have = [(q, a) for r in requests
            for q, a in zip(r["pool"], r["answers"]) if a is not None]
    if len(have) <= n:
        return have
    rng = np.random.default_rng([int(seed), 0x5A3F])
    pick = set(rng.choice(len(have), n, replace=False).tolist())
    works = [loaded.work(q) for q, _ in have]
    pick.add(max(range(len(have)),
                 key=lambda j: (works[j]["bytes"], works[j]["flop"])))
    return [have[j] for j in sorted(pick)]


def traced_works(requests: list, loaded, t_a: float, t_b: float) -> list:
    """The work of the searches answered in [t_a, t_b]: each answered
    request's, by the share of its time in flight that lies inside."""
    works = []
    for r in requests:
        if r["done"] is None or r["sent"] is None:
            continue
        span = max(r["done"] - r["sent"], 1e-9)
        share = max(0.0, min(r["done"], t_b) - max(r["sent"], t_a)) / span
        if share <= 0:
            continue
        for q, a in zip(r["pool"], r["answers"]):
            if a is not None:
                w = loaded.work(q)
                works.append({"flop": w["flop"] * share,
                              "bytes": w["bytes"] * share,
                              "batch_bytes": w["batch_bytes"]})
    return works


# --------------------------------------------------------------------------
# the traced interval
# --------------------------------------------------------------------------

class Tracer(threading.Thread):
    """Traces ``seconds`` of the window, ``offset`` after it starts, in
    this process (the one that holds the chip)."""

    def __init__(self, trace_dir: str, offset: float, seconds: float,
                 snapshot):
        super().__init__(daemon=True)
        self.trace_dir, self.offset, self.seconds = (trace_dir, offset,
                                                     seconds)
        self.snapshot = snapshot
        self.result = None
        self.error = None

    def run(self):
        import jax

        from benchmarks.trace import reduce as trace_reduce

        try:
            time.sleep(self.offset)
            # no Python tracer: it doubled the server's CPU a search and
            # its flush at stop_trace stalled the rest of the window; the
            # device planes and the annotation do not need it
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            try:
                before = self.snapshot()
                t_a = time.monotonic()
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    time.sleep(self.seconds)
                t_b = time.monotonic()
                after = self.snapshot()
            finally:
                jax.profiler.stop_trace()
            self.result = {"t_a": t_a, "t_b": t_b,
                           "counters": (before, after)}
        except Exception as e:  # reported by the run, which then fails
            self.error = e


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def device_or_exit(chips: int, rehearse: bool):
    """JAX's devices for this cell; exit 3 with nothing on standard output
    where there is no TPU or fewer chips than the cell asks for."""
    from elasticsearch_tpu.utils.platform import enable_compilation_cache

    if not rehearse:
        # (XLA:CPU executables read back from the persistent cache fail
        # with "Function ... not found" on this JAX; a rehearsal compiles)
        enable_compilation_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        return devices[:chips]
    if platform != "tpu" or len(devices) < chips:
        log(f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} device(s) of platform [{platform}]")
        sys.exit(NO_CHIP)
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    if peaks:
        return max(peaks)
    if devices[0].platform == "tpu":
        raise RuntimeError("the device reports no peak_bytes_in_use")
    import resource  # a CPU rehearsal: never printed, only checked for form

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _single(loaded, q: int) -> dict:
    return {"method": "POST", "path": loaded.path(q), "pool": [q],
            "body": json.dumps(loaded.request(q))}


def singles(loaded, pool: list, clients: int) -> dict:
    """Each entry of ``pool`` once, as a single request, dealt round
    ``clients`` callers who send back to back and then stop."""
    lists = [[] for _ in range(max(1, min(clients, len(pool))))]
    for n, q in enumerate(pool):
        lists[n % len(lists)].append(_single(loaded, int(q)))
    return {"mode": "closed", "once": True, "seconds": 0.0,
            "reply_timeout_s": 300.0, "requests": lists}


def at_once(loaded, pool: list, connections: int) -> dict:
    """Each entry of ``pool`` once, as a single request, all due at the
    same instant over ``connections`` connections."""
    return {"mode": "open", "seconds": 0.0, "connections": connections,
            "reply_timeout_s": 300.0,
            "requests": [dict(_single(loaded, int(q)), due=0.0)
                         for q in pool]}


def put_settings(transient: dict) -> dict:
    """One ``PUT /_cluster/settings`` of the program's dynamic settings (a
    value of None puts a setting back to its default)."""
    return {"mode": "closed", "once": True, "seconds": 0.0,
            "reply_timeout_s": 60.0, "requests": [[{
                "method": "PUT", "path": "/_cluster/settings", "pool": [],
                "body": json.dumps({"transient": transient})}]]}


def warm_up(files: CellFiles, gen: LoadGen, loaded, seed: int,
            seconds: float, rate, compiles) -> dict:
    """The last part of set-up: every shape the window will use, compiled.

    1. First touch: one search, alone. The program's lazy builders (dense
       impact block, the executor's copy of the slab) are not guarded
       against a burst of first requests: 64 at once each built a 4 GB
       copy and the host ran out of its 40 GiB (PR 23, chip call 2).
    2. Pool pass: each distinct query of the window's own schedule once,
       as single searches from a few callers back to back, so that the
       program for each query's shape class (chunk count x run length) is
       compiled or loaded from the cache before the window.
    3. Where the traffic file asks for them (``warmup.batches``), the
       coalescer's batch shapes, formed on purpose: under the program's
       own dynamic settings ``hold`` (every search parks, none is flushed
       early) and ``size_setting`` = n, n searches due at once make one
       batch of exactly n, for each n of ``sizes`` (the smallest of each
       padded shape). Which batches thread timing forms in a burst is
       luck, and a shape first met inside the window compiles there (a
       second's stall, PR 31). Where only some pool entries coalesce into
       a program of their own, ``of`` = {group: argument} names them and
       the configuration's kind lists them (``Loaded.group``); else the
       window's own first entries are sent. The settings go back to their
       defaults before anything else is sent.
    4. Rounds of the cell's own traffic from a seed offset, the first with
       bursts (the coalescer's batch shapes, as luck forms them), until a
       round compiles nothing more."""
    from benchmarks.loadgen import schedule as sched_mod

    warm = files.traffic["warmup"]
    out, last = {}, compiles()

    def phase(name, sched):
        nonlocal last
        t0 = time.monotonic()
        reqs = unpack(sched, gen.run(sched), loaded)
        now = compiles()
        row = {"phase": name, "seconds": round(time.monotonic() - t0, 3),
               "searches": sum(len(r["pool"]) for r in reqs),
               "compiles": now - last,
               "unanswered": sum(a is None for r in reqs
                                 for a in r["answers"])}
        last = now
        log(f"warm-up: {row}")
        out.setdefault("phases", []).append(row)
        return row

    def settle(transient: dict):
        sched = put_settings(transient)
        for r in unpack(sched, gen.run(sched), loaded):
            if r["status"] != 200:
                raise RuntimeError(f"the program refused {transient}: "
                                   f"status {r['status']}: {r['error']}")

    window = sched_mod.build(files.traffic, seed, seconds, rate, loaded)
    flat = (window["requests"] if window["mode"] == "open"
            else [r for lst in window["requests"] for r in lst])
    distinct = list(dict.fromkeys(q for r in flat for q in r["pool"]))
    phase("first touch", singles(loaded, distinct[:1], 1))
    phase("pool pass", singles(loaded, distinct[1:],
                               int(warm["pass_clients"])))
    forced = warm.get("batches")
    if forced:
        keys = [*forced["hold"], forced["size_setting"]]
        entries = distinct
        if "of" in forced:
            (group, arg), = forced["of"].items()
            entries = [int(q) for q in loaded.group(group, arg)]
        try:
            for n in (int(n) for n in forced["sizes"]):
                if len(entries) < n:
                    raise RuntimeError(
                        f"a batch of {n} asks for more entries than "
                        f"{forced.get('of', 'the window')} gives: "
                        f"{len(entries)}")
                settle({**forced["hold"], forced["size_setting"]: n})
                phase(f"batch of {n}", at_once(
                    loaded, entries[:n],
                    int(files.traffic.get("connections", n))))
        finally:
            settle(dict.fromkeys(keys))
    for i in range(int(warm["max_rounds"])):
        sched = sched_mod.build(
            dict(files.traffic, bursts=warm.get("bursts", []) if not i
                 else []),
            seed + 7919 * (i + 1), float(warm["seconds"]), rate, loaded)
        if not phase(f"round {i + 1}", sched)["compiles"]:
            break
    return out


def measure(files: CellFiles, gen: LoadGen, loaded, seed: int,
            seconds: float, rate, trace_dir, snapshot) -> dict:
    """The measured window: counters before, the load, counters after."""
    from benchmarks.loadgen import schedule as sched_mod

    sched = sched_mod.build(files.traffic, seed, seconds, rate, loaded)
    tracer = None
    if trace_dir:
        span = min(TRACE_SECONDS, seconds / 3.0)
        tracer = Tracer(trace_dir, max(0.25 * seconds, 0.2), span, snapshot)
    import gc

    watch = GcWatch()
    gc.callbacks.append(watch)
    before, cpu0 = snapshot(), time.process_time()
    pending = gen.start(sched)
    if tracer:
        tracer.start()
    result = gen.wait(pending)
    cpu1, after = time.process_time(), snapshot()
    gc.callbacks.remove(watch)
    if tracer:
        tracer.join(120.0)
        if tracer.is_alive() or tracer.error or not tracer.result:
            raise RuntimeError(f"the trace failed: {tracer.error!r}")
    reqs = unpack(sched, result, loaded)
    # open loop: the window is the offered [t0, t0 + seconds); closed
    # loop: it closes with the last reply of the requests started in it
    t0 = result["t0"]
    t_end = (t0 + seconds if sched["mode"] == "open" else result["t_end"])
    return {"mode": sched["mode"], "requests": reqs, "t0": t0,
            "t_end": t_end,
            "counters": (before, after), "host_cpu_s": cpu1 - cpu0,
            "gc": watch.story(),
            "traced": tracer.result if tracer else None}


def end_to_end(win: dict) -> dict:
    """Every end-to-end metric this window can report, by name."""
    reqs = win["requests"]
    answered = sum(a is not None for r in reqs for a in r["answers"])
    out = {}
    if win["mode"] == "open":
        lat = [(r["done"] - r["due"]) * 1000.0
               if r["done"] is not None and r["answers"][0] is not None
               else float("inf") for r in reqs]
        out["search_p50_ms"] = percentile(lat, 50)
        out["search_p95_ms"] = percentile(lat, 95)
    else:
        out["search_qps"] = answered / (win["t_end"] - win["t0"])
    return out


def observed(win: dict) -> dict:
    """What the harness reads from outside the program."""
    reqs = win["requests"]
    answered = sum(a is not None for r in reqs for a in r["answers"])
    obs = {"answered": answered}
    if answered:
        obs["host_cpu_ms"] = 1000.0 * win["host_cpu_s"] / answered
    late = [(r["sent"] - r["due"]) * 1000.0 for r in reqs
            if r["sent"] is not None]
    if late and win["mode"] == "open":
        obs["late_p95_ms"] = percentile(late, 95)
    obs.update(end_to_end(win))  # a reader may show one beside its layer
    return obs


def run_cell(args, table: dict, workload: str, rehearse: bool) -> dict:
    """Set-up, window, comparison; returns the line and the record."""
    from benchmarks import loaders, roofline
    from benchmarks.metrics import counters as counters_mod
    from benchmarks.metrics import read_metric
    from benchmarks.reference import check
    from benchmarks.trace import host_spans
    from benchmarks.trace import reduce as trace_reduce

    files = CellFiles(table, workload, rehearse)
    cell = files.cell
    # the program's own documented options that the configuration states
    # (each with its reason, under the file's "assumed")
    for key, value in files.config.get("environment", {}).items():
        os.environ[key] = str(value)
    devices = device_or_exit(cell["chips"], rehearse)
    kind = devices[0].device_kind
    stamps = {"imports": time.monotonic() - T_START}
    seed, seconds = int(args.seed), float(args.seconds)
    rate = files.own.get("rate_qps")
    if args.sweep:  # warm the shapes of the ladder's busiest step
        rate = max(float(x) for x in args.sweep.split(","))
    elif getattr(args, "rate", None):
        rate = float(args.rate)

    work_dir = os.path.join(HERE, "out", workload, f"work-{seed}")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    trace_dir = os.path.join(work_dir, "trace") if args.trace else None

    from elasticsearch_tpu.rest.server import RestServer

    loaded = loaders.load(files.config, seed, devices, rehearse)
    stamps["loaded"] = time.monotonic() - T_START
    log(f"{workload}: loaded {loaded.info} in "
        f"{stamps['loaded'] - stamps['imports']:.1f}s")
    server = RestServer(loaded.node, port=0)  # any free port
    server.start(background=True)
    gen = LoadGen(server.port, work_dir)

    def snapshot() -> dict:
        return counters_mod.parse(loaded.node.metrics.expose())

    def compiles() -> float:
        return counters_mod.total(
            snapshot(), [{"family": "estpu_program_compiles_total"}])

    record = {"workload": workload, "seed": seed, "seconds": seconds,
              "trace": int(args.trace), "rate_qps": rate,
              "loaded": loaded.info}
    try:
        record["warmup"] = warm_up(files, gen, loaded, seed, seconds, rate,
                                    compiles)
        stamps["warm"] = time.monotonic() - T_START
        if args.sweep:
            record["sweep"] = sweep(files, gen, loaded, seed, seconds,
                                    args.sweep, snapshot)
            return {"line": None, "record": record}
        win = measure(files, gen, loaded, seed, seconds, rate, trace_dir,
                      snapshot)
        peak = memory_peak(devices)
    finally:
        gen.close()
        server.stop()
    setup_s = win["t0"] - T_START
    stamps["window_t0"] = setup_s
    import resource

    record["host_peak_rss_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024
    loaded.node.close()
    loaded.node = None

    reqs = win["requests"]
    attempted = sum(len(r["pool"]) for r in reqs)
    unanswered = sum(a is None for r in reqs for a in r["answers"])
    for r in reqs:
        if r["error"]:
            log(f"a request failed: status {r['status']}: {r['error']}")
            break
    t0 = time.monotonic()
    sample = sample_answers(reqs, loaded, int(files.own["sample"]), seed)
    cmp = loaded.compare(sample)
    numbers = dict(cmp["numbers"], unanswered=unanswered)
    limits = files.own["limits"]
    correct = check.verdict(numbers, limits)
    record["reference_seconds"] = round(time.monotonic() - t0, 3)
    record["compared"] = {"answers": cmp["compared"], "faults": cmp["faults"]}
    if args.control:
        ctl = loaded.compare(loaded.control([q for q, _ in sample]))
        record["control"] = {"numbers": ctl["numbers"],
                             "correct": check.verdict(
                                 dict(ctl["numbers"], unanswered=0), limits)}
        log(f"control (the kind's own: one precision, or one guarantee, "
            f"down): {record['control']}")

    e2e = end_to_end(win)
    e2e["setup_s"] = setup_s
    obs = observed(win)
    values = dict(e2e)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": unanswered, "metrics": {}, "device": device}
    if args.trace:
        found = trace_reduce.find_trace(trace_dir)
        planes, window = trace_reduce.read_traced(found, rehearse)
        red = trace_reduce.reduce_events(planes, window)
        tr = win["traced"]
        device["window_s"], device["busy_s"] = red["window_s"], red["busy_s"]
        ctx = {"counters": {"window": win["counters"],
                            "traced": tr["counters"]},
               "observed": obs, "chips": cell["chips"],
               # a rehearsal has no chip: any row of the table exercises
               # the arithmetic, and nothing of it is printed
               "peaks": (roofline.peaks_for("TPU v5 lite") if rehearse
                         else roofline.peaks_for(kind)),
               "traced": {"busy_s": red["busy_s"],
                          "works": traced_works(reqs, loaded, tr["t_a"],
                                                tr["t_b"])}}
        for m in contract.metrics_of(table, workload, True):
            v = read_metric(m["name"], ctx)
            if v is not None:
                values[m["name"]] = v
        # the device's seconds by program and op, its idle seconds by
        # what the host was doing: the tables gap_report.py prints
        line["breakdown"] = host_spans.report(
            planes, window, host_spans.read_host_events(found))["breakdown"]
        record["trace"] = {k: red[k] for k in (
            "window_s", "busy_s", "busy_by_device")}
        if args.describe_trace:
            with open(os.path.join(HERE, "out", workload,
                                   f"{seed}.trace.txt"), "w") as fh:
                fh.write(trace_reduce.describe(found))
        if args.keep_trace:  # a trimmed copy, small enough for a fixture
            from benchmarks.trace import trim

            trim.trim(found, os.path.join(
                HERE, "out", workload, f"{seed}.trimmed.xplane.pb"))
    units = {m["name"]: m["unit"]
             for m in table["end_to_end"] + table["per_layer"]}
    line["metrics"] = {k: {"value": v, "unit": units[k]}
                       for k, v in values.items() if k in units}
    # the numbers compared, each beside its limit: last in the line
    line["compared"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in numbers.items()}
    record["counters"] = counter_story(win["counters"])
    record["gc"] = win["gc"]
    if win["mode"] == "open":
        record["latency_by_second"] = latency_story(win)
        # every answered request, [due s into the window, ms]: what a
        # shorter or longer window of this same run would have read
        record["latencies"] = [
            [round(r["due"] - win["t0"], 3),
             round((r["done"] - r["due"]) * 1000.0, 3)]
            for r in win["requests"] if r["done"] is not None]
    log(f"garbage collections in the window: {win['gc']}")
    story = record["counters"]
    log("counters over the window: " + json.dumps({
        "kernel_dispatch": story["kernel_dispatch"],
        "coalescer_flush": story["coalescer_flush"],
        "coalescer_bypass": story["coalescer_bypass"],
        "compiles": sum(story["compiles"].values()),
        "compile_cache": story["compile_cache"]}))
    record.update({"stamps_s": stamps, "observed": obs, "line": line,
                   "window_s": win["t_end"] - win["t0"]})
    shutil.rmtree(work_dir, ignore_errors=True)
    return {"line": line, "record": record}


class GcWatch:
    """Collections of the server process's garbage collector over the
    window, timed (for the record: a full collection walks every container
    of a multi-million-document segment)."""

    def __init__(self):
        self.rows = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.rows.append((info["generation"],
                              time.perf_counter() - self._t))

    def story(self) -> dict:
        out = {}
        for gen, secs in self.rows:
            row = out.setdefault(f"gen{gen}", {"n": 0, "seconds": 0.0,
                                               "longest_s": 0.0})
            row["n"] += 1
            row["seconds"] += secs
            row["longest_s"] = max(row["longest_s"], secs)
        return out


def latency_story(win: dict) -> list:
    """[second of the window, requests due in it, p50 ms, max ms] for the
    record: where in the window the tail came from."""
    rows: dict = {}
    for r in win["requests"]:
        if r["done"] is not None:
            rows.setdefault(int(r["due"] - win["t0"]), []).append(
                (r["done"] - r["due"]) * 1000.0)
    return [[sec, len(v), round(percentile(v, 50), 2), round(max(v), 2)]
            for sec, v in sorted(rows.items())]


def counter_story(pair) -> dict:
    """For the record (stderr and the out file), not for a metric: which
    kernels and programs the window's searches went through."""
    before, after = pair

    def rise(family):
        rows = after.get(family, {})
        return {k: v - before.get(family, {}).get(k, 0.0)
                for k, v in rows.items()
                if v - before.get(family, {}).get(k, 0.0)}

    top = sorted(rise("estpu_program_execute_seconds").items(),
                 key=lambda kv: -kv[1])[:12]
    return {"kernel_dispatch": rise("estpu_kernel_dispatch_total"),
            "coalescer_flush": rise("estpu_coalescer_flush_total"),
            "coalescer_bypass": rise("estpu_coalescer_bypass_total"),
            "compiles": rise("estpu_program_compiles_total"),
            "compile_cache": rise("estpu_compile_cache_events_total"),
            "program_execute_seconds_top": top}


def sweep(files, gen, loaded, seed, seconds, rates, snapshot) -> list:
    """One set-up, then the window at each rate of the ladder: does the
    reply rate keep up, and is a backlog left growing at the end?"""
    steps = []
    for i, rate in enumerate(float(x) for x in rates.split(",")):
        win = measure(files, gen, loaded, seed + 101 * (i + 1), seconds,
                      rate, None, snapshot)
        reqs = win["requests"]
        lat = [(r["done"] - r["due"]) * 1000.0 for r in reqs
               if r["done"] is not None]
        tail = [x for r, x in zip(reqs, lat) if r["due"] - win["t0"]
                > 0.75 * seconds]
        head = [x for r, x in zip(reqs, lat) if r["due"] - win["t0"]
                < 0.25 * seconds]
        drain = max((r["done"] for r in reqs if r["done"] is not None),
                    default=win["t_end"]) - win["t_end"]
        steps.append({
            "rate_qps": rate, "requests": len(reqs),
            "unanswered": sum(a is None for r in reqs for a in r["answers"]),
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "p50_first_quarter_ms": percentile(head, 50) if head else None,
            "p50_last_quarter_ms": percentile(tail, 50) if tail else None,
            "drain_after_close_s": drain,
            "host_cpu_ms": 1000.0 * win["host_cpu_s"] / max(len(reqs), 1)})
        log(f"sweep: {steps[-1]}")
    return steps


def rehearse(table: dict, args) -> int:
    """Every cell's whole path at a thousand documents on the CPU. Not a
    measurement: prints no line and no time."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    names = ([args.workload] if args.workload
             else [c["name"] for c in table["workloads"] if c["chips"] == 1])
    for name in names:
        cell = contract.cell_of(table, name)
        for trace in (0, 1):
            a = argparse.Namespace(**vars(args))
            a.trace, a.seconds, a.sweep = trace, 2.0, None
            done = run_cell(a, table, name, True)
            line = done["line"]
            contract.check_last_line(line, cell, bool(trace), table)
            contract.dumps_line(line)
            if not line["correct"]:
                log(f"rehearsal of {name}: not correct: {line['compared']} "
                    f"{done['record']['compared']}")
                return 1
            if args.control and done["record"]["control"]["correct"]:
                log(f"rehearsal of {name}: the control came out correct: "
                    f"{done['record']['control']}")
                return 1
    log("rehearsal passed")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="a trial at another rate than the cell's (builder)")
    ap.add_argument("--describe-trace", action="store_true")
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args(argv)

    # nothing but the line may reach standard output: keep the descriptor
    # for it and point fd 1 at standard error for everything else
    # (logging, the profiler, native libraries)
    sys.stdout.flush()
    line_fd = os.dup(1)
    os.dup2(2, 1)
    table = contract.load_table()
    if args.rehearse:
        return rehearse(table, args)
    if not args.workload:
        ap.error("--workload is required")
    if args.seconds is None:
        args.seconds = float(table["run_seconds"])
    cell = contract.cell_of(table, args.workload)
    done = run_cell(args, table, args.workload, False)
    out_dir = os.path.join(HERE, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.seed}.json"), "w") as fh:
        json.dump(done["record"], fh, indent=1, default=str)
    line = done["line"]
    if line is None:  # a sweep: no line
        return 0
    contract.check_last_line(line, cell, bool(args.trace), table)
    text = contract.dumps_line(line)
    for name, v in line["compared"].items():
        print(f"compared {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    sys.stderr.flush()
    os.write(line_fd, (text + "\n").encode())
    os.close(line_fd)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except contract.ContractError as e:
        log(f"the last line would not meet the contract: {e}")
        code = 4
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:  # reported, then the exit below
        import traceback

        traceback.print_exc()
        code = 1
    # daemon threads of the server's pool must not hold the exit, nor may
    # anything write after the line
    sys.stderr.flush()
    os._exit(code)
