"""Print what a profiler trace holds: planes, their lines and event
counts, and the device ops that took most time, as JSON.

    python chipbench/tools/inspect_trace.py <trace dir>

Look at a trace by hand with this before writing a reader against it.
"""
from __future__ import annotations

import json
import sys
from collections import Counter, defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.trace import latest_xplane  # noqa: E402


def main(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    path = latest_xplane(Path(trace_dir))
    pd = ProfileData.from_file(str(path))
    out = {"file": str(path), "bytes": path.stat().st_size, "planes": []}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            n, secs = Counter(), defaultdict(float)
            first = None
            for ev in line.events:
                n[ev.name] += 1
                secs[ev.name] += ev.duration_ns / 1e9
                if first is None:
                    first = {"name": ev.name, "start_ns": ev.start_ns,
                             "duration_ns": ev.duration_ns,
                             "stats": [[str(k), str(v)[:200]] for k, v in ev.stats][:12]}
            top = sorted(secs.items(), key=lambda kv: -kv[1])[:25]
            lines.append({"name": line.name, "events": sum(n.values()),
                          "top": [[k, v, n[k]] for k, v in top], "first": first})
        out["planes"].append({"name": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1]), indent=1))
