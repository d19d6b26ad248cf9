"""The trace reduction on a recorded trace: a trimmed copy of the first
traced run of msmarco-passage-shard.match-steady on a v5e (PR 23, chip
call 2), read through the same reader as a run's trace."""
import os

import pytest

from benchmarks.trace import host_spans
from benchmarks.trace import reduce as trace_reduce
from benchmarks.trace import trim

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(trim.__file__)),
                       "fixtures", "match-steady.v5e.trimmed.xplane.pb")


def test_recorded_trace_gives_busy_inside_the_window():
    red = trace_reduce.reduce_file(FIXTURE)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert set(red["busy_by_device"]) == {0}
    ops = host_spans.device_ops(*trace_reduce.read_traced(FIXTURE))
    assert ops and len(ops) <= 10
    assert all(secs > 0 for _, secs in ops)
    assert len({name for name, _ in ops}) == len(ops)


def test_recorded_trace_has_the_planes_and_lines_the_reducer_reads():
    planes, window = trace_reduce.read_planes(FIXTURE)
    assert window is not None and window[1] > window[0]
    device = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p)]
    assert device == ["/device:TPU:0"]
    lines = planes[device[0]]
    assert trace_reduce.OPS_LINE in lines
    # a device plane's other lines cover the same time again: summing the
    # lines would count it more than once
    ops = trace_reduce.union_seconds(
        [(s, s + d) for _, s, d in lines[trace_reduce.OPS_LINE]],
        *window)
    every = sum(trace_reduce.union_seconds(
        [(s, s + d) for _, s, d in evs], *window) for evs in lines.values())
    assert len(lines) > 1 and every > ops


def test_trimmer_round_trips_through_the_real_reader(tmp_path):
    planes = [("/device:TPU:0", [
        ("XLA Ops", [("fusion.1", 1000.0, 500.0), ("copy.2", 1400.0, 300.0)]),
        ("Steps", [("step", 900.0, 5000.0)])]),
        ("/host:CPU", [("python3", [(trace_reduce.WINDOW, 1100.0, 4500.0)])])]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(trim.encode_space(planes))
    red = trace_reduce.reduce_file(str(path))
    assert red["window_s"] == pytest.approx(4.5e-6)
    assert red["busy_s"] == pytest.approx(0.6e-6)
