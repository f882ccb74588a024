"""The trace reduction: busy time as the union of op intervals, kernel
time by name, idle gaps named by the host span around them."""
import json
from pathlib import Path

import pytest

from chipbench.trace import Trace, latest_xplane

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    ops = {0: [("fusion.1", 0, 100), ("kernel_a", 50, 150),
               ("fusion.2", 200, 300), ("kernel_a", 400, 450)],
           1: [("fusion.1", 0, 500)]}
    spans = [("engine.decode_window", 0, 500), ("engine.boundary", 140, 260)]
    return Trace(ops, spans)


def test_busy_is_the_union_of_op_intervals():
    tr = synthetic()
    assert tr.busy_intervals(0) == [(0, 150), (200, 300), (400, 450)]
    assert tr.busy_s([0]) == pytest.approx(300e-9)
    # averaged over chips: (300 + 500) / 2 ns
    assert tr.busy_s([0, 1]) == pytest.approx(400e-9)


def test_kernel_and_op_seconds():
    tr = synthetic()
    assert tr.kernel_seconds([0], r"kernel_a") == pytest.approx(150e-9)
    assert tr.top_ops([0], 2) == [["kernel_a", pytest.approx(150e-9)],
                                  ["fusion.1", pytest.approx(100e-9)]]


def test_gaps_named_by_innermost_host_span():
    tr = synthetic()
    assert tr.idle_gaps(0) == [(150, 200), (300, 400)]
    assert tr.longest_gaps(0) == [["engine.decode_window", pytest.approx(100e-9)],
                                  ["engine.boundary", pytest.approx(50e-9)]]


def test_recorded_chip_trace():
    """A small trace recorded on a TPU v5e by
    chipbench/tools/record_test_trace.py: three steps of a matmul and a
    Pallas kernel, each in a "test.step" span, 20 ms host pauses between."""
    host = json.loads((DATA / "small_trace" / "host.json").read_text())
    tr = Trace.from_file(latest_xplane(DATA / "small_trace"),
                         span_names={"test.step", "test.pause"})
    assert list(tr.ops) == [0]
    busy = tr.busy_s([0])
    assert 0 < busy < host["window_s"]
    assert tr.kernel_seconds([0], r"test_add_kernel") > 0
    names = [n for n, _ in tr.longest_gaps(0, 2)]
    # the two longest idle gaps are the host's pauses between steps
    assert names == ["test.pause", "test.pause"]
    assert all(s >= host["pause_s"] * 0.9 for _, s in tr.longest_gaps(0, 2))
