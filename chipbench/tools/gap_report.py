"""The device's idle gaps in a traced run, named by the program's host
spans once the device timeline is aligned with the host's.

    python chipbench/tools/gap_report.py <trace dir> [--top 10]

A profiler trace puts device events on a clock that runs apart from the
host's: on a TPU v5e the device timeline comes out early by more than a
millisecond.  Pairs of events that must come in a known order bound the
offset (device time + offset = host time):

* a program cannot start on the device before the host issued it: for
  each run, offset >= start of the host's
  ``tpu::System::Execute=>IssueSequencedEvent`` (paired with the device
  module by the ``run_id`` of the ``DoEnqueueProgram`` inside it) minus
  the module's start;
* the host's ``CompleteCallbacks`` of a run (same ``run_id``) come after
  the module ends: offset <= their start minus the module's end.

The device timeline is shifted by the lower bound.  Each idle stretch
between the chip's first and last op is then given to the innermost
host span around it (the most recently opened one), and the report
prints the offset's bounds, the longest gaps, and each span's share of
the idle time.  The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.trace import (DEVICE_PLANE, HOST_PLANE, Trace,  # noqa: E402
                             latest_xplane)

# the host spans the program writes (repro.serving.engine,
# repro.frontdoor.server)
PROGRAM_SPANS = ("frontdoor.pump", "frontdoor.stream_tokens",
                 "frontdoor.deliver", "frontdoor.submit", "engine.tick",
                 "engine.boundary", "engine.prefill_chunk",
                 "engine.decode_window", "engine.device")
NO_SPAN = "host: no span"
EXECUTE = "tpu::System::Execute=>IssueSequencedEvent"
ENQUEUE = "DoEnqueueProgram"
CALLBACKS = "CompleteCallbacks"
MODULES_LINE = "XLA Modules"


def _run_id(ev):
    for k, v in ev.stats:
        if k == "run_id":
            return int(v)
    return None


def clock_offset(pd, device: int = 0):
    """(low, high, pairs): bounds in ns on the offset that carries device
    times onto the host's clock, and the number of runs paired; (None,
    None, 0) when the trace holds no such pairs (a CPU trace)."""
    modules = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != device:
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    rid = _run_id(ev)
                    if rid is not None:
                        s = int(ev.start_ns)
                        modules[rid] = (s, s + int(ev.duration_ns))
    launched, completed = {}, {}
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            open_exec = None           # the execute event last opened here
            for ev in line.events:
                s = int(ev.start_ns)
                if ev.name == EXECUTE:
                    open_exec = (s, s + int(ev.duration_ns))
                elif ev.name == ENQUEUE:
                    rid = _run_id(ev)
                    if rid is None:
                        continue
                    inside = open_exec is not None and \
                        open_exec[0] <= s <= open_exec[1]
                    launched[rid] = open_exec[0] if inside else s
                elif ev.name == CALLBACKS:
                    rid = _run_id(ev)
                    if rid is not None:
                        completed[rid] = s
    lows = [launched[r] - modules[r][0] for r in modules if r in launched]
    highs = [completed[r] - modules[r][1] for r in modules if r in completed]
    if not lows:
        return None, None, 0
    return max(lows), (min(highs) if highs else None), len(lows)


def shifted(tr: Trace, offset_ns: int) -> Trace:
    """The trace with every device op moved by ``offset_ns``."""
    ops = {d: [(op[0], op[1] + offset_ns, op[2] + offset_ns, *op[3:])
               for op in v] for d, v in tr.ops.items()}
    return Trace(ops, tr.spans)


def idle_by_span(tr: Trace, device: int = 0):
    """Idle ns of one chip between its first and last op, split among
    the innermost host span around each stretch: ({name: ns}, total)."""
    gaps = tr.idle_gaps(device)
    points = []                     # (t, order, kind, payload)
    for k, (name, s, e) in enumerate(tr.spans):
        points.append((s, 1, "open", (k, s, e, name)))
        points.append((e, 0, "close", k))
    for s, e in gaps:
        points.append((s, 2, "gap", 1))
        points.append((e, 0, "gap", -1))
    points.sort(key=lambda p: (p[0], p[1]))
    active: dict[int, tuple] = {}
    idle = defaultdict(int)
    in_gap = 0
    prev = None
    for t, _, kind, payload in points:
        if in_gap > 0 and prev is not None and t > prev:
            if active:
                # the innermost: the latest opened, the shortest on a tie
                k = max(active, key=lambda j: (active[j][1], -active[j][2]))
                idle[active[k][3]] += t - prev
            else:
                idle[NO_SPAN] += t - prev
        prev = t
        if kind == "open":
            active[payload[0]] = payload
        elif kind == "close":
            active.pop(payload, None)
        else:
            in_gap += payload
    return dict(idle), sum(e - s for s, e in gaps)


def report(trace_dir, *, device: int = 0, top: int = 10,
           span_names=PROGRAM_SPANS) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(latest_xplane(Path(trace_dir))))
    low, high, pairs = clock_offset(pd, device)
    tr = Trace.from_profile(pd, set(span_names))
    if low is not None:
        tr = shifted(tr, low)
    idle, total = idle_by_span(tr, device)
    return {"offset_ms": {"low": None if low is None else low / 1e6,
                          "high": None if high is None else high / 1e6,
                          "runs_paired": pairs},
            "idle_s": total / 1e9,
            "longest_gaps": tr.longest_gaps(device, top),
            "idle_share": {k: v / total for k, v in
                           sorted(idle.items(), key=lambda kv: -kv[1])}
            if total else {}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    out = report(args.trace_dir, top=args.top)
    off = out["offset_ms"]
    print(f"clock offset: {off['low']} to {off['high']} ms "
          f"({off['runs_paired']} runs paired)")
    print(f"idle between the first and last op: {out['idle_s']:.6f} s")
    for name, share in out["idle_share"].items():
        print(f"  {share:8.4%}  {name}")
    for name, secs in out["longest_gaps"]:
        print(f"  gap {secs * 1e3:9.3f} ms  {name}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
