"""The engine's own C3-SL dispatch record equals the grouping log the
benchmark reads from its wrappers (ScheduleRecorder.resolve()): same
kinds, rows and order, on the tiny cell, windows in which slots finish
mid-window included."""
import json

import pytest

from chipbench.tests import tiny

# turn the engine's record on from construction (the warm-up request,
# uid -1, comes before the benchmark's log starts and is left out), and
# write both logs out when ``drivers/serve.py`` resolves its own
PATCH = """
import json
from chipbench import harness
from repro.serving import engine as _engine
_engines = []
_init = _engine.BatchedEngine.__init__
def __init__(self, *a, **k):
    _init(self, *a, **k)
    self.record_dispatches()
    _engines.append(self)
_engine.BatchedEngine.__init__ = __init__
_load = harness.load_module
def load_module(path, name):
    mod = _load(path, name)
    if name.startswith("chipbench_driver_"):
        resolve = mod.ScheduleRecorder.resolve
        def wrapped(self):
            log = resolve(self)
            mine = [e for e in _engines[-1].dispatch_record
                    if any(r[1] != -1 for r in e[2])]
            with open({out!r}, "w") as f:
                json.dump({{"recorder": log, "engine": mine,
                           "faults": self.faults}}, f)
            return log
        mod.ScheduleRecorder.resolve = wrapped
    return mod
harness.load_module = load_module
"""


@pytest.mark.parametrize("seed", [8, 3000000202])
def test_engine_record_equals_recorder(tmp_path, seed):
    out = tmp_path / "logs.json"
    res = tiny.run_in_child(tmp_path, seed=seed, patch=PATCH.format(out=str(out)))
    assert res["correct"] is True
    logs = json.loads(out.read_text())
    assert logs["faults"] == []
    rec = [(k, rows) for _, k, rows in logs["recorder"]]
    mine = [(k, rows) for _, k, rows in logs["engine"]]
    assert {k for k, _ in mine} == {"P", "D"}
    assert mine == rec
    # slots finished mid-window: a later step of one window (same
    # dispatch time) carries fewer rows than an earlier one
    eng = logs["engine"]
    assert any(a[0] == b[0] and a[1] == b[1] == "D" and len(b[2]) < len(a[2])
               for a, b in zip(eng, eng[1:]))
