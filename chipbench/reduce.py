"""From a traced run to what the per-layer metric readers need: the
reduced trace, the device's busy and window seconds, the breakdown the
ledger keeps, and the device's peaks."""
from __future__ import annotations

from pathlib import Path

from chipbench import harness
from chipbench.trace import Trace, latest_xplane


def reduce(pctx: dict, bench_dir: Path) -> dict:
    tr = Trace.from_file(latest_xplane(pctx["trace_dir"]),
                         span_names=set(pctx["spans"]))
    devices = pctx["devices"]
    t_start, t_end = pctx["trace_window"]
    return {"trace": tr,
            "busy_s": tr.busy_s(devices),
            "window_s": t_end - t_start,
            "breakdown": {"device_ops": tr.top_ops(devices, 10),
                          "idle_gaps": tr.longest_gaps(devices[0], 10)},
            "peaks": harness.load_peaks(pctx["device"]["kind"], bench_dir)}
