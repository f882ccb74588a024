"""Run one cell of the chip benchmark.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``chipbench/configs/<file>.json``, whose
``kind`` picks ``chipbench/drivers/<kind>.py``) and a traffic mix
(``chipbench/traffic/<name>.json``).  Everything is found by the names in
``BENCHMARK.json``.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and prints its per-layer metrics
(``chipbench/metrics/<name>.py``), the device's busy and window seconds,
and a breakdown of device time and idle gaps.  Both check the served
output against the plain reference and say so in ``correct``; the numbers
compared, each beside its limit, end standard error and the result line.

Exits non-zero, printing no result, without a TPU or with fewer chips than
the cell needs.  The last line of standard output is one JSON object.
"""
from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax() -> None:
    """The program's own compile-cache set-up, plus caching of every
    program however quickly it compiled, so a warm run compiles nothing."""
    import jax
    from repro.launch.runtime import configure_jax as program_configure
    program_configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run(argv=None, *, root: Path = ROOT, devices_for=harness.check_devices,
        extra: dict | None = None) -> dict:
    """One run; returns the result object (the caller prints it).
    ``devices_for(chips)`` finds the devices; tests give one that skips the
    look for a chip."""
    args = parse(argv)
    clock = harness.Clock()
    clock.t0 = _T_START
    spec = harness.load_spec(root)
    cell = harness.find_cell(spec, args.workload)
    conf = harness.load_config(spec, cell["config"], root)
    bench_dir = root / "chipbench"
    configure_jax()
    devices = devices_for(cell["chips"])
    counter = harness.CompileCounter()
    driver = harness.load_module(bench_dir / "drivers" / f"{conf['kind']}.py",
                                 f"chipbench_driver_{conf['kind']}")
    trace_dir = None
    if args.trace:
        trace_dir = root / ".chipbench" / "trace" / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    ctx = {"conf": conf, "cell": cell, "traffic": cell["traffic"],
           "traffic_dir": bench_dir / "traffic", "seed": args.seed,
           "seconds": args.seconds, "trace_dir": trace_dir,
           "devices": devices, "counter": counter, "clock": clock,
           **(extra or {})}
    result = driver.run(ctx)
    if args.trace:
        pctx = result.pop("per_layer_ctx")
        from chipbench import reduce
        pctx.update(reduce.reduce(pctx, bench_dir))
        result["metrics"] = harness.read_per_layer(spec, cell["name"], pctx,
                                                   bench_dir)
        result["device"]["busy_s"] = pctx["busy_s"]
        result["device"]["window_s"] = pctx["window_s"]
        result["breakdown"] = pctx["breakdown"]
    else:
        names = {m["name"] for m in harness.cell_metrics(spec, cell["name"],
                                                         "end_to_end")}
        result["metrics"] = {k: v for k, v in result["metrics"].items()
                             if k in names}
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except harness.RunError as e:
        harness.log(f"no result: {e}")
        return 2
    harness.print_checks(result["checks"])
    print(harness.result_line(
        correct=result["correct"], attempted=result["attempted"],
        failed=result["failed"], metrics=result["metrics"],
        device=result["device"], checks=result["checks"],
        breakdown=result.get("breakdown")), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
