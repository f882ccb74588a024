"""The per-layer metrics that read the program's own counters and spans
(queue_wait_ms, prefill_row_use, kv_page_use, kv_reserved_use,
engine_host_ms_per_dispatch) return numbers on the tiny cell under
--trace 1."""
import json

from chipbench.tests import tiny

NEW = {"queue_wait_ms": ("ms", "lower", "program_counter"),
       "prefill_row_use": ("%", "higher", "program_counter"),
       "kv_page_use": ("%", "higher", "program_counter"),
       "kv_reserved_use": ("%", "higher", "program_counter"),
       "engine_host_ms_per_dispatch": ("ms", "lower", "device_trace")}


def test_program_metrics_read_on_the_tiny_cell(tmp_path):
    root = tiny.make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": s, "layer": "test",
         "moves": "gen_tokens_per_s"} for n, (u, b, s) in NEW.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    res = tiny.run_in_child(tmp_path, seed=34, trace=1)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items() if k in NEW}
    assert set(got) == set(NEW)
    assert got["queue_wait_ms"] >= 0
    assert 0 < got["prefill_row_use"] <= 100
    assert 0 < got["kv_page_use"] <= 100
    # a resident request's written pages lie inside its reservation
    assert got["kv_page_use"] <= got["kv_reserved_use"] <= 100
    assert got["engine_host_ms_per_dispatch"] > 0
