"""From a profiler trace to idle seconds by phase, and device seconds by
XLA module.

The program's tracer (``elasticsearch_tpu/tracing/tracer.py``) holds a
``jax.profiler.TraceAnnotation(name, t=<trace id>)`` open for every span,
so a traced run's ``.xplane.pb`` carries the spans on its host planes, on
the clock of the device planes. ``span_names.json`` lists the names this
module reads, by kind: a *leaf* is a phase (leaves never nest in one
another), a *container* holds leaves, and ``rest.pool_wait`` is derived,
because a wait that starts on one thread and ends on another has no
annotation.

Pure functions in the manner of ``reduce.reduce_events``: the tests feed
them synthetic planes, ``tools/gap_report.py`` and ``run.py`` a real trace:
``report`` is the one function both read, and its ``breakdown`` is what a
traced run's last line carries.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import Counter

from benchmarks.trace import reduce as trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
#: the line of a device plane whose events are whole XLA programs, named
#: after the jitted function
MODULES_LINE = "XLA Modules"
NO_SPAN = "no span open, request in flight"
NO_REQUEST = "no request in the server"
POOL_WAIT = "rest.pool_wait"
OTHER_PHASES = "other phases"


def load_names(path: str | None = None) -> dict:
    """``span_names.json`` and then every file of the directory
    ``span_names/`` beside it, in the order of their names: each may add
    ``containers``, ``leaves`` and ``derived`` (a later PR's spans come as
    a file of their own), and may not restate ``root`` or a name that is
    already listed."""
    path = path or os.path.join(HERE, "span_names.json")
    with open(path) as fh:
        names = json.load(fh)
    more = os.path.splitext(path)[0]
    for extra in sorted(glob.glob(os.path.join(more, "*.json"))):
        with open(extra) as fh:
            add = json.load(fh)
        if "root" in add:
            raise ValueError(f"{extra} restates [root]")
        for key in ("containers", "leaves", "derived"):
            have = (set(names["containers"]) | set(names["leaves"])
                    | set(names["derived"]))
            again = sorted(set(add.get(key, ())) & have)
            if again:
                raise ValueError(f"{extra} restates {again}")
            if key == "derived":
                names[key] = {**names[key], **add.get(key, {})}
            else:
                names[key] = names[key] + list(add.get(key, ()))
    return names


def span_events(host: list, names: dict) -> list:
    """``host``: [(event name, start ns, duration ns, trace id or "")] of
    the trace's host planes. Returns the listed spans as (name, start ns,
    end ns, trace id), and one derived ``rest.pool_wait`` for every root
    whose trace has a second event: from the root's start to the first
    other event of its trace that starts inside it."""
    listed = set(names["containers"]) | set(names["leaves"])
    out = [(n, float(s), float(s) + float(d), t) for n, s, d, t in host
           if n in listed]
    first: dict = {}
    for n, s, e, t in out:
        if t and n != names["root"] and s < first.get(t, float("inf")):
            first[t] = s
    for n, s, e, t in list(out):
        if n == names["root"] and s <= first.get(t, float("inf")) < e:
            out.append((POOL_WAIT, s, first[t], t))
    return sorted(out, key=lambda ev: (ev[1], ev[2]))


def device_intervals(planes: dict, line: str = trace_reduce.OPS_LINE):
    """(event name, start ns, end ns) of ``line`` on the first device."""
    for name, lines in sorted(planes.items()):
        if trace_reduce.DEVICE_PLANE.match(name):
            return [(ev, s, s + d) for ev, s, d in lines.get(line, [])]
    raise ValueError(f"the trace holds no device plane: {sorted(planes)}")


def idle_intervals(planes: dict, window: tuple) -> list:
    """Every interval inside the window in which no operation ran on the
    first device's ``XLA Ops`` line: [(start ns, end ns)], in order."""
    lo, hi = float(window[0]), float(window[1])
    busy = [(s, e) for _, s, e in device_intervals(planes)]
    gaps = trace_reduce.idle_gaps(busy, lo, hi, top=len(busy) + 1)
    return sorted((at, at + secs * 1e9) for at, secs in gaps)


def idle_by_phase(idle: list, events: list, names: dict) -> dict:
    """Idle seconds by phase. Each instant of an idle interval goes to the
    leaf spans open at it, in equal shares when several are open (several
    requests in flight); with no leaf open, to ``NO_SPAN`` where a
    container is open and to ``NO_REQUEST`` where none is. The values sum
    to the idle time."""
    leaves = set(names["leaves"]) | set(names["derived"])
    containers = set(names["containers"])
    points = []
    for n, s, e, _ in events:
        if e > s and (n in leaves or n in containers):
            key = n if n in leaves else None
            points.append((s, 1, key))
            points.append((e, -1, key))
    points.sort(key=lambda p: p[0])
    open_leaves: Counter = Counter()
    state = {"leaves": 0, "containers": 0}
    out: dict = {}

    def apply(delta, key):
        if key is None:
            state["containers"] += delta
        else:
            state["leaves"] += delta
            open_leaves[key] += delta

    def credit(a, b):
        secs = (b - a) / 1e9
        if secs <= 0:
            return
        if state["leaves"]:
            for key, n in open_leaves.items():
                if n:
                    out[key] = out.get(key, 0.0) + secs * n / state["leaves"]
        else:
            key = NO_SPAN if state["containers"] else NO_REQUEST
            out[key] = out.get(key, 0.0) + secs

    i = 0
    for a, b in sorted(idle):
        while i < len(points) and points[i][0] <= a:
            apply(points[i][1], points[i][2])
            i += 1
        at = a
        while i < len(points) and points[i][0] < b:
            credit(at, points[i][0])
            at = points[i][0]
            apply(points[i][1], points[i][2])
            i += 1
        credit(at, b)
    return out


def _clipped(events: list, lo: float, hi: float):
    for name, s, e in events:
        part = min(e, hi) - max(s, lo)
        if part > 0:
            yield name, s, part / 1e9


def device_seconds_by_module(planes: dict, window: tuple) -> list:
    """[[module name, seconds inside the window]], most first: the events
    of the first device's ``XLA Modules`` line, which carry the jitted
    function's name."""
    acc: dict = {}
    for name, _, secs in _clipped(device_intervals(planes, MODULES_LINE),
                                  float(window[0]), float(window[1])):
        acc[name] = acc.get(name, 0.0) + secs
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]


def _ops_in_modules(planes: dict, lo: float, hi: float):
    """(op name, module name or "", seconds inside the window) of every
    device op: the XLA module whose run the op's event started in."""
    mods = sorted(device_intervals(planes, MODULES_LINE),
                  key=lambda m: m[1])
    starts = [m[1] for m in mods]
    for name, s, secs in _clipped(device_intervals(planes), lo, hi):
        j = bisect.bisect_right(starts, s) - 1
        yield name, (mods[j][0] if j >= 0 and s < mods[j][2] else ""), secs


def modules_of_ops(planes: dict, window: tuple, top: int = 10) -> list:
    """[[op name, seconds, {module name: seconds}]] for the ``top`` device
    ops by time in the window: which XLA module's run each op's events
    started in (an op with no module round it is filed under "")."""
    acc: dict = {}
    for name, mod, secs in _ops_in_modules(planes, float(window[0]),
                                           float(window[1])):
        row = acc.setdefault(name, [0.0, {}])
        row[0] += secs
        row[1][mod] = row[1].get(mod, 0.0) + secs
    rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:top]
    return [[name, total, dict(sorted(by.items(), key=lambda kv: -kv[1]))]
            for name, (total, by) in rows]


_LAYOUT = re.compile(r"\{[^{}]*\}")
#: a row's name in a line's ``breakdown``: what the ledger keeps of it
PRINTED = 64


def printed_op(module: str, op: str) -> str:
    """``<jitted function>/<instruction> <result type>``, at most
    ``PRINTED`` characters: the module without its fingerprint, the HLO
    instruction without layouts and operands. One op of one function in
    many shape classes prints one name."""
    head, sep, rest = op.partition(" = ")
    if sep:
        rest, depth = _LAYOUT.sub("", rest), 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if ch == " " and not depth:
                rest = rest[:i]
                break
        op = f"{head} {rest}"
    module, op = module.partition("(")[0], op.lstrip("%")
    return (f"{module}/{op}" if module else op)[:PRINTED]


def device_ops(planes: dict, window: tuple, top: int = 10) -> list:
    """[[printed name, seconds]] of the first device's ops inside the
    window, most first: rows whose printed names are equal are one row."""
    acc: dict = {}
    for name, mod, secs in _ops_in_modules(planes, float(window[0]),
                                           float(window[1])):
        key = printed_op(mod, name)
        acc[key] = acc.get(key, 0.0) + secs
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:top]]


def top_rows(rows: list, top: int, rest: str) -> list:
    """The ``top`` - 1 largest of [[name, seconds]] and what is left under
    one name, so that the rows still add up."""
    rows = sorted(rows, key=lambda kv: -kv[1])
    if len(rows) <= top:
        return rows
    return rows[:top - 1] + [[rest, sum(v for _, v in rows[top - 1:])]]


def read_host_events(path: str) -> list:
    """(event name, start ns, duration ns, trace id or "") of every event
    on the trace's host planes; the trace id is the ``t`` stat the
    program's annotations carry."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                trace = ""
                for key, value in e.stats:
                    if key == "t":
                        trace = str(value)
                out.append((e.name, float(e.start_ns),
                            float(e.duration_ns), trace))
    return out


def report(planes: dict, window: tuple, host: list,
           names: dict | None = None, top: int = 10) -> dict:
    """The two tables of a trace, and what they rest on."""
    names = names or load_names()
    events = span_events(host, names)
    idle = idle_intervals(planes, window)
    by_phase = idle_by_phase(idle, events, names)
    idle_s = sum((b - a) for a, b in idle) / 1e9
    named = sum(v for k, v in by_phase.items() if k != NO_SPAN)
    return {
        "window_s": (float(window[1]) - float(window[0])) / 1e9,
        "idle_s": idle_s,
        "idle_intervals": len(idle),
        "span_events": len(events),
        "idle_by_phase": [[k, v] for k, v in sorted(
            by_phase.items(), key=lambda kv: -kv[1])],
        # the share of the idle time that has a name: a phase, or that
        # no request was in the server
        "idle_named_share": named / idle_s if idle_s > 0 else 1.0,
        "device_by_module": device_seconds_by_module(planes, window),
        "ops_in_modules": modules_of_ops(planes, window, top),
        # what a run's last line carries (``run.py``), under names a
        # reader of the ledger can plan from
        "breakdown": {
            "device_ops": device_ops(planes, window, top),
            "idle_gaps": top_rows([[k, v] for k, v in by_phase.items()],
                                  top, OTHER_PHASES)},
    }
