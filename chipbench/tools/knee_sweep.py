"""Find the knee of a serve configuration under an open-loop mix: the
highest offered rate it sustains.  Run once, on the chip, when a cell at
a fixed rate is defined (at about four fifths of the knee for a cell
judged on its tails, above it for one judged on completed tokens).

    python chipbench/tools/knee_sweep.py --config deepseek-7b-split-serve \\
        --traffic chat_burst --rates 1 2 3 4 6 --seconds 30 --seed 1

For each rate, in one process: the mix with ``rate_rps`` replaced, one
run of the serve driver (set-up, a window of ``--seconds``, no reference
check), and a line with the offered and completed requests per second,
the tokens per second and the TTFT and inter-token tails.  A rate is
sustained when the window completes at least 95% of what it was offered
and the requests sent in its last third wait no longer for their first
token than those in its first third (no growing backlog).
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, run  # noqa: E402
from chipbench.drivers import serve  # noqa: E402


def one_rate(conf, mix_name, tdir: Path, rate: float, seed: int, seconds: float,
             devices, counter) -> dict:
    mix = json.loads((harness.BENCH_DIR / "traffic" / f"{mix_name}.json").read_text())
    mix["rate_rps"] = rate
    (tdir / f"{mix_name}.json").write_text(json.dumps(mix))
    clock = harness.Clock()
    res = serve.run({"conf": conf, "traffic": mix_name, "traffic_dir": tdir,
                     "seed": seed, "seconds": seconds, "trace_dir": None,
                     "devices": devices, "counter": counter, "clock": clock,
                     "check": False})
    s = res["served"]
    sent = [r for r in s.records if s.t0 <= r["t_send"] < s.t1]
    done = [r for r in sent if r["err"] is None and r["t_done"] < s.t1]
    third = (s.t1 - s.t0) / 3

    def ttft(rs):
        v = [r["bursts"][0][0] - r["t_send"] for r in rs
             if r["err"] is None and r["bursts"]]
        return harness.percentile(v, 90) if v else float("inf")

    early = [r for r in sent if r["t_send"] < s.t0 + third]
    late = [r for r in sent if r["t_send"] >= s.t1 - third]
    row = {"rate_rps": rate,
           "completed_rps": len(done) / (s.t1 - s.t0),
           "completed_share": len(done) / max(len(sent), 1),
           "ttft_p90_first_third_ms": 1e3 * ttft(early),
           "ttft_p90_last_third_ms": 1e3 * ttft(late),
           **{k: v["value"] for k, v in res["metrics"].items()}}
    row["sustained"] = (row["completed_share"] >= 0.95 and
                        row["ttft_p90_last_third_ms"]
                        <= 1.25 * row["ttft_p90_first_third_ms"])
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    run.configure_jax()
    devices = harness.check_devices(1)
    conf = json.loads((harness.BENCH_DIR / "configs" / f"{args.config}.json").read_text())
    counter = harness.CompileCounter()
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        tdir = Path(tmp) / "traffic"
        shutil.copytree(harness.BENCH_DIR / "traffic", tdir,
                        ignore=shutil.ignore_patterns("__pycache__"))
        for rate in args.rates:
            row = one_rate(conf, args.traffic, tdir, rate, args.seed,
                           args.seconds, devices, counter)
            rows.append(row)
            print(json.dumps(row), flush=True)
    knee = max((r["rate_rps"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
