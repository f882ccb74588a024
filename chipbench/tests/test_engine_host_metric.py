"""engine_host_ms_per_dispatch: tick time less the device spans inside
it, over dispatches, with a region the benchmark also wraps under the
program's own name counted once."""
from chipbench import harness

MOD = harness.load_module(
    harness.BENCH_DIR / "metrics" / "engine_host_ms_per_dispatch.py",
    "chipbench_metric_engine_host_ms_per_dispatch")


def test_host_time_per_dispatch():
    ms = 1_000_000
    spans = [
        ("engine.tick", 0, 10 * ms),
        ("engine.prefill_chunk", 1 * ms, 5 * ms),
        ("engine.prefill_chunk", 1 * ms, 5 * ms),     # the benchmark's wrap
        ("engine.device", 2 * ms, 4 * ms),
        ("engine.decode_window", 5 * ms, 9 * ms),
        ("engine.device", 6 * ms, 8 * ms),
        ("engine.tick", 20 * ms, 24 * ms),
        ("engine.device", 21 * ms, 22 * ms),
        ("engine.device", 30 * ms, 31 * ms),          # outside every tick
    ]
    # host: (10 - 2 - 2) + (4 - 1) ms over 2 dispatches
    assert MOD.host_ms_per_dispatch(spans) == 4.5


def test_nothing_to_read():
    assert MOD.host_ms_per_dispatch([("engine.tick", 0, 5)]) is None
    assert MOD.read({"trace_dir": None}) is None
