"""Readings for the limits of a serve configuration's check, on the chip.

    python chipbench/tools/control.py --config deepseek-7b-split-serve \\
        --traffic long_batch --seconds 45 --seeds 101 102 103 \\
        [--out control.jsonl]

``--config`` is a name under ``chipbench/configs/`` or a path to a
configuration file.  For each seed, in one process: a run of the serve
driver (set-up, a window of ``--seconds``, the check) that reports the
program's numbers against the float32 reference, and the control's: the
reference one step of precision down (the configuration's
``check.control``) put in the program's place, its first choice at each
served position read in the reference's logits, and judged by the
check's own limits (``correct`` must come out false).  The limits are
set from these readings (the program's largest, the control's
smallest); the benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, run  # noqa: E402
from chipbench.drivers import serve  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    path = Path(args.config)
    if not path.is_file():
        path = harness.BENCH_DIR / "configs" / f"{args.config}.json"
    conf = json.loads(path.read_text())
    run.configure_jax()
    devices = harness.check_devices(1)
    counter = harness.CompileCounter()
    rows = []
    for seed in args.seeds:
        t = time.monotonic()
        res = serve.run({"conf": conf, "traffic": args.traffic,
                         "traffic_dir": harness.BENCH_DIR / "traffic",
                         "seed": seed, "seconds": args.seconds,
                         "trace_dir": None, "devices": devices,
                         "counter": counter, "clock": harness.Clock(),
                         "control": True})
        row = {"config": conf["name"], "seed": seed, "program": res["program_numbers"],
               "program_correct": res["program_correct"],
               "control": res["numbers"], "correct": res["correct"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "checks": res["checks"], "seconds": time.monotonic() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    for name in rows[0]["program"]:
        prog = [r["program"][name] for r in rows]
        ctl = [r["control"][name] for r in rows]
        harness.log(f"{name}: program max {max(prog)!r}, control min "
                    f"{min(ctl)!r} over {len(rows)} seeds")
    harness.log(f"control correct: {[r['correct'] for r in rows]}; program "
                f"correct: {[r['program_correct'] for r in rows]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
