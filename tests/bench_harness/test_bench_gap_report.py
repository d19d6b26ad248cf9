"""tools/gap_report.py's own arithmetic: the phase table out of two
counter snapshots, and how it adds up."""
import pytest

from benchmarks.tools import gap_report
from benchmarks.trace import host_spans


def _snap(rows):
    """{span: (wall s, count, self s, cpu s, errors)} -> a parsed scrape."""
    out = {fam: {} for fam in gap_report.FAMILIES.values()}
    for name, vals in rows.items():
        for key, v in zip(("wall", "n", "self", "cpu", "errors"), vals):
            out[gap_report.FAMILIES[key]][f'span="{name}"'] = v
    return out


BEFORE = _snap({"rest.request": (1.0, 10, 0.1, 0.05, 0),
                "search": (0.8, 10, 0.2, 0.3, 0)})
AFTER = _snap({"rest.request": (3.0, 30, 0.3, 0.15, 0),
               "search": (2.6, 30, 0.5, 0.9, 0),
               "device.wait": (1.0, 20, 1.0, 0.0, 0),
               "search.plan": (0.4, 40, 0.4, 0.0, 0),
               "msearch.batch_attempt": (0.1, 2, 0.1, 0.1, 2),
               "never.closed": (0.0, 0, 0.0, 0.0, 0)})


def test_phase_table_is_the_rise_a_search_answered():
    t = gap_report.phase_table((BEFORE, AFTER), 20)
    assert "never.closed" not in t
    assert t["rest.request"] == {
        "ms": pytest.approx(100.0), "self_ms": pytest.approx(10.0),
        "cpu_ms": pytest.approx(5.0), "spans": pytest.approx(1.0),
        "errors": 0}
    assert t["search.plan"]["spans"] == pytest.approx(2.0)
    assert t["msearch.batch_attempt"]["errors"] == 2


def test_leaves_and_containers_self_add_up_to_the_roots():
    names = host_spans.load_names()
    t = gap_report.phase_table((BEFORE, AFTER), 20)
    got = gap_report.sums(t, names)
    assert got["request_ms"] == pytest.approx(100.0)
    assert got["leaves_ms"] == pytest.approx(50.0 + 20.0)
    # rest.request 10 + search 15 + msearch.batch_attempt 5
    assert got["containers_self_ms"] == pytest.approx(30.0)
    assert got["leaves_plus_self_over_roots"] == pytest.approx(1.0)
