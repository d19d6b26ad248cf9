"""A trimmed copy of a real trace, small enough to commit as a fixture.

Reads an ``.xplane.pb`` through ``jax.profiler.ProfileData`` and writes the
window annotation and the first events of every device line back out in
the same wire format (XSpace > XPlane > XLine > XEvent, with the event
names as XEventMetadata), by hand: the protobuf schema's Python module is
not installed here, and five message types do not need it. The fixture
then goes through the same reader as a run's trace.

    python3 benchmarks/trace/trim.py <in.xplane.pb> <out.xplane.pb> [events a line]
"""
from __future__ import annotations

import sys


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def encode_space(planes: list) -> bytes:
    """``planes``: [(plane name, [(line name, [(event name, start ns,
    duration ns), ...]), ...]), ...] -> serialized XSpace."""
    out = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        meta: dict = {}
        body = _field(1, pid) + _field(2, pname)
        for lid, (lname, events) in enumerate(lines, 1):
            base = min((int(s) for _, s, _ in events), default=0)
            line = _field(1, lid) + _field(2, lname) + _field(3, base)
            for name, start, dur in events:
                mid = meta.setdefault(name, len(meta) + 1)
                line += _field(4, _field(1, mid)
                               + _field(2, int(round((start - base) * 1000)))
                               + _field(3, int(round(dur * 1000))))
            body += _field(3, line)
        for name, mid in meta.items():
            entry = _field(1, mid) + _field(2, _field(1, mid)
                                            + _field(2, name))
            body += _field(4, entry)
        out += _field(1, body)
    return out


def trim(path_in: str, path_out: str, per_line: int = 400) -> dict:
    from jax.profiler import ProfileData

    from benchmarks.trace.reduce import DEVICE_PLANE, WINDOW

    planes, kept = [], {}
    for plane in ProfileData.from_file(path_in).planes:
        lines = []
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name):
                evs = [(e.name[:120], float(e.start_ns),
                        float(e.duration_ns))
                       for _, e in zip(range(per_line), line.events)]
            else:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events if e.name == WINDOW]
            if evs:
                lines.append((line.name, evs))
                kept[f"{plane.name}/{line.name}"] = len(evs)
        if lines:
            planes.append((plane.name, lines))
    with open(path_out, "wb") as fh:
        fh.write(encode_space(planes))
    return kept


if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    print(trim(sys.argv[1], sys.argv[2],
               int(sys.argv[3]) if len(sys.argv) > 3 else 400))
