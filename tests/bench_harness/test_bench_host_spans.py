"""trace/host_spans.py on synthetic planes: idle seconds by phase sum to
the idle time, overlapping requests share an instant equally, the pool
wait is derived from the trace ids, and device ops find their module."""
import json
import os

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


# ---- the line's breakdown: names one can plan from -----------------------------

SCATTER = ("%fusion.1 = f32[4194304]{0:T(1024)S(1)} fusion(f32[4194304]"
           "{0:T(1024)S(1)} %while.2, s32[%d]{0:T(1024)S(1)} %gte.42), "
           "kind=kCustom, calls=%fused_computation.1")
TOPK = ("%custom-call = (f32[512,10]{1,0:T(8,128)S(1)}, s32[512,10]{1,0:"
        "T(8,128)S(1)}) custom-call(f32[512,8192]{1,0:T(8,128)S(1)} "
        "%reshape.13), custom_call_target=\"TopK\"")


def test_a_printed_op_is_the_function_the_instruction_and_its_result():
    mod = "jit_bm25_term_group_topk(2502640247167472124)"
    assert host_spans.printed_op(mod, SCATTER.replace("%d", "131072")) == (
        "jit_bm25_term_group_topk/fusion.1 f32[4194304]")
    assert host_spans.printed_op(mod, TOPK) == (
        "jit_bm25_term_group_topk/custom-call (f32[512,10], s32[512,10])")
    assert host_spans.printed_op("", "copy.9") == "copy.9"
    long = host_spans.printed_op("jit_" + "x" * 80, TOPK)
    assert len(long) == host_spans.PRINTED


def test_device_ops_with_one_printed_name_are_one_row():
    """One op of one function in several shape classes (other operands,
    other fingerprints) was several rows of PR 30's line."""
    modules = [("jit_bm25_term_group_topk(1)", 19, 12),
               ("jit_bm25_term_group_topk(2)", 39, 6),
               ("jit_knn_topk(3)", 59, 4)]
    ops = [(SCATTER.replace("%d", "131072"), 20, 6), (TOPK, 26, 4),
           (SCATTER.replace("%d", "196608"), 40, 3), (TOPK, 43, 1),
           (TOPK, 60, 2), ("copy.9", 70, 1)]
    rows = host_spans.device_ops(_planes(ops, modules), WINDOW)
    assert rows == [
        ["jit_bm25_term_group_topk/fusion.1 f32[4194304]",
         pytest.approx(9e-3)],
        ["jit_bm25_term_group_topk/custom-call (f32[512,10], s32[512,10])",
         pytest.approx(5e-3)],
        ["jit_knn_topk/custom-call (f32[512,10], s32[512,10])",
         pytest.approx(2e-3)],
        ["copy.9", pytest.approx(1e-3)]]
    assert len({name for name, _ in rows}) == len(rows)
    assert host_spans.device_ops(_planes(ops, modules), WINDOW, top=2) == (
        rows[:2])
    # clipped to the window like every other table
    assert host_spans.device_ops(_planes(ops, modules), (0.0, 23 * MS)) == [
        [rows[0][0], pytest.approx(3e-3)]]


def test_top_rows_keep_the_sum():
    rows = [[f"p{i}", float(i)] for i in range(1, 14)]
    got = host_spans.top_rows(rows, 10, "other phases")
    assert len(got) == 10 and got[0] == ["p13", 13.0]
    assert got[-1] == ["other phases", 1.0 + 2.0 + 3.0 + 4.0]
    assert sum(v for _, v in got) == sum(v for _, v in rows)
    assert host_spans.top_rows(rows[:3], 10, "x") == [
        ["p3", 3.0], ["p2", 2.0], ["p1", 1.0]]


def test_the_reports_breakdown_is_the_lines():
    """``idle_gaps`` by phase in place of PR 23's two names (a request in
    flight or none): the same trace, now saying which phase held the
    device back; its rows add up to the idle time."""
    modules = [("jit_bm25_term_group_topk(1)", 19, 30)]
    rep = host_spans.report(_planes(OPS, modules), WINDOW, HOST, NAMES)
    bd = rep["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    gaps = dict(bd["idle_gaps"])
    assert gaps[host_spans.NO_REQUEST] == pytest.approx(0.050)
    assert gaps["device.wait"] == pytest.approx(0.0105)
    assert gaps[host_spans.NO_SPAN] == pytest.approx(0.006)
    assert bd["idle_gaps"] == rep["idle_by_phase"]  # under ten phases here
    assert sum(gaps.values()) == pytest.approx(rep["idle_s"])
    assert bd["device_ops"] == [
        ["jit_bm25_term_group_topk/fusion.1", pytest.approx(10e-3)],
        ["jit_bm25_term_group_topk/custom-call", pytest.approx(4e-3)]]
    # the form check_last_line takes: at most ten [name, seconds] rows
    for rows in bd.values():
        assert len(rows) <= 10 and all(
            isinstance(n, str) and isinstance(s, float) for n, s in rows)


# ---- span names come as files ---------------------------------------------------

def _names_dir(tmp_path, **files):
    base = tmp_path / "span_names.json"
    base.write_text(json.dumps({
        "root": "rest.request", "containers": ["rest.request", "search"],
        "leaves": ["search.plan"], "derived": {"rest.pool_wait": "x"}}))
    (tmp_path / "span_names").mkdir()
    for name, body in files.items():
        (tmp_path / "span_names" / f"{name}.json").write_text(
            json.dumps(body))
    return str(base)


def test_span_names_are_the_table_and_then_every_file_beside_it(tmp_path):
    path = _names_dir(
        tmp_path,
        b_bulk={"containers": ["bulk"], "leaves": ["bulk.translog"]},
        a_aggs={"what": "x", "leaves": ["search.aggregate"],
                "derived": {"bulk.queue": "how it is derived"}})
    names = host_spans.load_names(path)
    assert names["root"] == "rest.request"
    assert names["containers"] == ["rest.request", "search", "bulk"]
    assert names["leaves"] == ["search.plan", "search.aggregate",
                               "bulk.translog"]  # files in name order
    assert set(names["derived"]) == {"rest.pool_wait", "bulk.queue"}
    # the added leaf is a phase like any other
    by = host_spans.idle_by_phase(
        [(0.0, 10 * MS)], [("bulk", 0.0, 10 * MS, "A"),
                           ("bulk.translog", 2 * MS, 6 * MS, "A")], names)
    assert by == {host_spans.NO_SPAN: pytest.approx(6e-3),
                  "bulk.translog": pytest.approx(4e-3)}


@pytest.mark.parametrize("body", [
    {"root": "bulk"}, {"root": "rest.request"},
    {"leaves": ["search.plan"]}, {"containers": ["search.plan"]},
    {"leaves": ["search"]}, {"derived": {"rest.pool_wait": "again"}},
    {"leaves": ["rest.pool_wait"]}])
def test_a_names_file_may_not_restate_what_is_listed(tmp_path, body):
    with pytest.raises(ValueError, match="restates"):
        host_spans.load_names(_names_dir(tmp_path, more=body))


def test_the_committed_table_stands_alone_where_the_directory_is_missing():
    here = os.path.dirname(host_spans.__file__)
    extra = [f for f in os.listdir(here) if f == "span_names"]
    names = host_spans.load_names()
    with open(os.path.join(here, "span_names.json")) as fh:
        table = json.load(fh)
    if not extra:  # this PR adds no file there
        assert names == table
    assert names["root"] == table["root"]
