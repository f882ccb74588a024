"""Benchmark entrypoint: one section per paper table + system benches.

    PYTHONPATH=src python -m benchmarks.run [--fast]

--fast skips the accuracy-trend training runs (several minutes on CPU).
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--accuracy-steps", type=int, default=300)
    args = ap.parse_args()

    from repro.launch.runtime import configure_jax
    configure_jax()

    from benchmarks import (bench_accuracy, bench_codec_latency, bench_comm,
                            bench_roofline, bench_serving, bench_table1,
                            bench_table2)

    sections = [
        ("table2_formulas", bench_table2.main),
        ("table1_columns", bench_table1.main),
        # --fast shortens the adaptive-R sweep; both write BENCH_comm.json
        ("comm_bytes", lambda: bench_comm.main(smoke=args.fast)),
        ("codec_latency", bench_codec_latency.main),
        # --fast runs the smoke variant (seconds); both write BENCH_serving.json
        ("serving_throughput", lambda: bench_serving.main(smoke=args.fast)),
        # backend + paged-read sweeps; both write BENCH_roofline.json
        ("roofline_sweeps", lambda: bench_roofline.main(smoke=args.fast)),
    ]
    for name, fn in sections:
        print(f"\n==== {name} ====", flush=True)
        t0 = time.time()
        fn()
        print(f"# section {name}: {time.time()-t0:.1f}s", flush=True)

    if not args.fast:
        print("\n==== table1_accuracy_trend (laptop-scale) ====", flush=True)
        bench_accuracy.main(steps=args.accuracy_steps)


if __name__ == "__main__":
    main()
