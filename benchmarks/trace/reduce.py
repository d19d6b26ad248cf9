"""From a profiler trace (``.xplane.pb``) to device busy seconds.

``window_s`` is the traced interval on the trace's own clock: the span of
the ``WINDOW`` annotation that ``run.py`` holds open, in the process that
holds the chip, from just after ``start_trace`` to just before
``stop_trace``. Per device plane (``/device:TPU:<i>``) busy is the UNION of
the intervals of ONE line — the line that carries the XLA operations —
clipped to the window: a device's several lines (steps, modules, ops)
overlap, and a sum over them or over devices would pass the window.
``busy_s`` is the mean of the devices' figures (one device's where the
cell has one chip), never a sum.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the line of a device plane that carries the XLA operations
OPS_LINE = "XLA Ops"


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals (ns), clipped to
    [lo, hi), in seconds."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if min(e, hi) > max(s, lo))
    total, cur_s, cur_e = 0.0, None, None
    for s, e in clipped:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def idle_gaps(intervals, lo: float, hi: float, top: int = 10) -> list:
    """The longest gaps between busy intervals inside [lo, hi): (start ns,
    seconds), longest first."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if min(e, hi) > max(s, lo))
    gaps, at = [], lo
    for s, e in clipped:
        if s > at:
            gaps.append((at, (s - at) / 1e9))
        at = max(at, e)
    if hi > at:
        gaps.append((at, (hi - at) / 1e9))
    return sorted(gaps, key=lambda g: -g[1])[:top]


def reduce_events(planes: dict, window: tuple) -> dict:
    """``planes``: {plane name: {line name: [(event name, start ns,
    duration ns), ...]}}; ``window``: (start ns, end ns). Pure: the tests
    feed it synthetic events, ``reduce_file`` a real trace. (The tables of
    a trace — device seconds by op, idle seconds by phase — are
    ``host_spans.report``'s.)"""
    lo, hi = float(window[0]), float(window[1])
    if not hi > lo:
        raise ValueError(f"the traced window is empty: {window}")
    busy = {}
    for name, lines in sorted(planes.items()):
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        events = lines.get(OPS_LINE)
        if events is None:
            raise ValueError(
                f"device plane [{name}] has no [{OPS_LINE}] line; it has "
                f"{sorted(lines)}")
        busy[int(m.group(1))] = union_seconds(
            [(s, s + d) for _, s, d in events], lo, hi)
    if not busy:
        raise ValueError(
            f"the trace holds no device plane; planes: {sorted(planes)}")
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy.values()) / len(busy),
            "busy_by_device": busy}


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


#: a CPU rehearsal has no device plane; the XLA CPU client's threads
#: stand in for one, so that the path is exercised (never a measurement)
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"


def read_planes(path: str, cpu_rehearsal: bool = False):
    """(planes as ``reduce_events`` takes them, window or None)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, window, stand_in = {}, None, []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            if cpu_rehearsal and line.name.startswith(CPU_CLIENT_LINE):
                stand_in += [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.duration_ns > 0]
            if device:
                lines[line.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
                continue
            for e in line.events:
                if e.name == WINDOW:
                    window = (float(e.start_ns),
                              float(e.start_ns) + float(e.duration_ns))
        planes[plane.name] = lines
    if cpu_rehearsal:
        planes["/device:TPU:0"] = {OPS_LINE: stand_in}
    return planes, window


def read_traced(path: str, cpu_rehearsal: bool = False):
    """``read_planes`` of a run's trace: the window has to be there."""
    planes, window = read_planes(path, cpu_rehearsal)
    if window is None:
        raise ValueError(f"no [{WINDOW}] annotation in {path}")
    return planes, window


def reduce_file(path: str, cpu_rehearsal: bool = False) -> dict:
    return reduce_events(*read_traced(path, cpu_rehearsal))


def describe(path: str, events: int = 4) -> str:
    """Planes, lines and the first events of each, for the look by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:events]:
                out.append(f"    {e.name[:80]!r} start_ns={e.start_ns} "
                           f"duration_ns={e.duration_ns}")
    return "\n".join(out)
