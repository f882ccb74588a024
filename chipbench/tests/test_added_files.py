"""A configuration, a traffic mix and a per-layer metric added as new
files only (in a copy of the benchmark) are found by name and run."""
from chipbench.tests import tiny


def test_new_files_run_by_name(tmp_path):
    res = tiny.run_in_child(tmp_path, seed=21, trace=1)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"served_requests_per_s"}
    assert res["metrics"]["served_requests_per_s"]["value"] > 0
    assert res["device"]["window_s"] > 0
    assert {"device_ops", "idle_gaps"} <= set(res["breakdown"])
