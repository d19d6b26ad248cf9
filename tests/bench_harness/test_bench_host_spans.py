"""trace/host_spans.py on synthetic planes: idle seconds by phase sum to
the idle time, overlapping requests share an instant equally, the pool
wait is derived from the trace ids, and device ops find their module."""
import pytest

from benchmarks.trace import host_spans
from benchmarks.trace import reduce as trace_reduce

NAMES = host_spans.load_names()
MS = 1e6  # ns


def _planes(ops, modules=()):
    return {"/device:TPU:0": {
        trace_reduce.OPS_LINE: [(n, s * MS, d * MS) for n, s, d in ops],
        host_spans.MODULES_LINE: [(n, s * MS, d * MS)
                                  for n, s, d in modules]},
        "/host:CPU": {}}


def _host(rows):
    return [(n, s * MS, d * MS, t) for n, s, d, t in rows]


# two requests that overlap, then a stretch with no request at all
HOST = _host([
    ("rest.request", 0, 40, "A"),
    ("search.body_json", 4, 2, "A"),     # opens on the worker's line
    ("search", 6, 30, "A"),
    ("search.plan", 6, 4, "A"),
    ("device.dispatch", 10, 6, "A"),
    ("device.wait", 16, 14, "A"),
    ("rest.respond", 37, 3, "A"),
    ("rest.request", 10, 40, "B"),
    ("search", 12, 34, "B"),
    ("search.plan", 12, 10, "B"),
    ("device.wait", 24, 20, "B"),
    ("bench_window", 0, 100, ""),
    ("some other annotation", 0, 100, ""),
])
# the device works 20..30 and 40..44 ms of a 100 ms window
OPS = [("fusion.1", 20, 10), ("custom-call", 40, 4)]
WINDOW = (0.0, 100 * MS)


def test_span_events_keep_the_vocabulary_and_derive_the_pool_wait():
    events = host_spans.span_events(HOST, NAMES)
    names = [e[0] for e in events]
    assert "some other annotation" not in names
    assert "bench_window" not in names
    waits = [e for e in events if e[0] == host_spans.POOL_WAIT]
    # request A: from its start to its trace's first other event; request
    # B likewise
    assert [(e[1] / MS, e[2] / MS, e[3]) for e in waits] == [
        (0.0, 4.0, "A"), (10.0, 12.0, "B")]
    # a root with no trace id, or alone in its trace, has no derived wait
    lone = host_spans.span_events(_host([("rest.request", 0, 5, ""),
                                         ("rest.request", 9, 5, "C")]),
                                  NAMES)
    assert [e[0] for e in lone] == ["rest.request", "rest.request"]


def test_idle_intervals_are_all_of_them_in_order():
    idle = host_spans.idle_intervals(_planes(OPS), WINDOW)
    assert [(a / MS, b / MS) for a, b in idle] == [
        (0.0, 20.0), (30.0, 40.0), (44.0, 100.0)]


def test_idle_seconds_by_phase_sum_to_the_idle_time():
    events = host_spans.span_events(HOST, NAMES)
    idle = host_spans.idle_intervals(_planes(OPS), WINDOW)
    by = host_spans.idle_by_phase(idle, events, NAMES)
    idle_s = sum(b - a for a, b in idle) / 1e9
    assert sum(by.values()) == pytest.approx(idle_s)
    ms = {k: v * 1e3 for k, v in by.items()}
    # 0..4 pool wait of A; 4..6 body_json; 6..10 plan of A (B's pool wait
    # shares 10..12 with A's dispatch; B's plan shares 12..16 with it,
    # then 16..20 with A's wait)
    assert ms[host_spans.POOL_WAIT] == pytest.approx(4 + 2 * 0.5)
    assert ms["search.body_json"] == pytest.approx(2)
    assert ms["search.plan"] == pytest.approx(4 + 4 * 0.5 + 4 * 0.5)
    assert ms["device.dispatch"] == pytest.approx(2 * 0.5 + 4 * 0.5)
    # A's wait has 16..20 inside the idle time, shared with B's plan; B's
    # wait 24..44 has 30..40 of it, the last 3 ms beside A's respond
    assert ms["device.wait"] == pytest.approx(4 * 0.5 + 7 + 3 * 0.5)
    assert ms["rest.respond"] == pytest.approx(3 * 0.5)
    # 44..46: B's search is open with no leaf, 46..50 its root alone: a
    # request in flight and no span; after 50 no request in the server
    assert ms[host_spans.NO_SPAN] == pytest.approx(6)
    assert ms[host_spans.NO_REQUEST] == pytest.approx(50)


def test_an_instant_is_shared_equally_among_the_open_leaves():
    host = _host([("rest.request", 0, 10, "A"), ("device.wait", 0, 10, "A"),
                  ("rest.request", 0, 10, "B"), ("device.wait", 0, 10, "B"),
                  ("rest.request", 0, 10, "C"), ("search.plan", 0, 10, "C")])
    events = [e for e in host_spans.span_events(host, NAMES)
              if e[0] != host_spans.POOL_WAIT]
    by = host_spans.idle_by_phase([(0.0, 9 * MS)], events, NAMES)
    assert by["device.wait"] == pytest.approx(6e-3)
    assert by["search.plan"] == pytest.approx(3e-3)


def test_device_seconds_by_module_and_the_ops_inside():
    modules = [("jit_bm25_score_hybrid_gather(123)", 19, 12),
               ("jit_topk_with_mask(7)", 39, 6),
               ("jit_bm25_score_hybrid_gather(123)", 150, 10)]  # outside
    ops = OPS + [("fusion.1", 41, 1), ("copy.9", 60, 2)]
    planes = _planes(ops, modules)
    by = host_spans.device_seconds_by_module(planes, WINDOW)
    assert by == [["jit_bm25_score_hybrid_gather(123)",
                   pytest.approx(12e-3)],
                  ["jit_topk_with_mask(7)", pytest.approx(6e-3)]]
    rows = host_spans.modules_of_ops(planes, WINDOW, top=2)
    assert rows[0][0] == "fusion.1" and rows[0][1] == pytest.approx(11e-3)
    assert rows[0][2] == {
        "jit_bm25_score_hybrid_gather(123)": pytest.approx(10e-3),
        "jit_topk_with_mask(7)": pytest.approx(1e-3)}
    assert rows[1][0] == "custom-call"
    # an op that ran under no module is filed under ""
    rows = host_spans.modules_of_ops(planes, WINDOW, top=3)
    assert rows[2] == ["copy.9", pytest.approx(2e-3),
                       {"": pytest.approx(2e-3)}]


def test_report_names_its_share_and_needs_a_device_plane():
    rep = host_spans.report(_planes(OPS), WINDOW, HOST, NAMES)
    assert rep["idle_s"] == pytest.approx(0.086)
    assert rep["idle_intervals"] == 3
    assert sum(v for _, v in rep["idle_by_phase"]) == pytest.approx(0.086)
    assert rep["idle_named_share"] == pytest.approx((86 - 6) / 86)
    with pytest.raises(ValueError):
        host_spans.idle_intervals({"/host:CPU": {}}, WINDOW)
