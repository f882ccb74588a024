"""The gap report: the host-device clock offset bounded by causal pairs
of events, and idle time given to the innermost host span around it."""
from pathlib import Path

import pytest

from chipbench.tools import gap_report
from chipbench.trace import Trace

DATA = Path(__file__).resolve().parent / "data" / "small_trace"


def test_recorded_chip_trace_offset_and_gaps():
    """On the small v5e trace (three steps in "test.step" spans, 20 ms
    "test.pause" spans between) the device modules start 1.24-1.25 ms
    before the host issued them, and the host's callbacks follow their
    ends by 1.78 ms or more."""
    out = gap_report.report(DATA, span_names=("test.step", "test.pause"),
                            top=2)
    off = out["offset_ms"]
    assert off["runs_paired"] == 3
    assert 1.24 <= off["low"] <= off["high"] <= 1.79
    assert [n for n, _ in out["longest_gaps"]] == ["test.pause", "test.pause"]
    assert out["idle_share"]["test.pause"] > 0.8
    assert sum(out["idle_share"].values()) == pytest.approx(1.0)


def test_idle_goes_to_the_innermost_span():
    ops = {0: [("a", 0, 100), ("b", 200, 300), ("c", 500, 600)]}
    spans = [("engine.tick", 50, 450), ("engine.boundary", 150, 250),
             ("engine.device", 180, 220)]
    idle, total = gap_report.idle_by_span(Trace(ops, spans))
    # gaps 100-200 and 300-500
    assert total == 300
    assert idle == {"engine.tick": 50 + 150, "engine.boundary": 30,
                    "engine.device": 20, gap_report.NO_SPAN: 50}


def test_shift_moves_device_ops_only():
    tr = Trace({0: [("a", 0, 10, "text")]}, [("x", 5, 8)])
    moved = gap_report.shifted(tr, 3)
    assert moved.ops[0] == [("a", 3, 13, "text")]
    assert moved.spans == tr.spans
