"""Reduction of a JAX profiler trace to device busy time, op and kernel
time, and idle gaps attributed to what the host was doing.

Built on ``jax.profiler.ProfileData`` (the ``.xplane.pb`` the profiler
writes under ``<dir>/plugins/profile/<run>/``).  A TPU's plane is named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed
operation, with a start and a duration in nanoseconds on the clock of
the host planes (to within about a millisecond on a v5e), whose events
include the benchmark's own ``TraceAnnotation`` spans.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# ops that contain other ops of the same line (a loop's body runs inside
# it): counted in busy time, left out of per-op time
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def latest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


class Trace:
    """Device op intervals per chip and host spans, from one trace.

    An op is ``(name, start_ns, end_ns)`` or ``(name, start_ns, end_ns,
    text)``, where ``text`` is the HLO instruction the trace names it by
    (kept for custom calls, whose operands identify a kernel)."""

    def __init__(self, ops: dict[int, list[tuple]],
                 spans: list[tuple[str, int, int]]):
        self.ops = {d: sorted(v, key=lambda e: e[1]) for d, v in ops.items()}
        self.spans = sorted(spans, key=lambda e: e[1])

    @classmethod
    def from_file(cls, path: Path, span_names=None) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(str(path)), span_names)

    @classmethod
    def from_profile(cls, pd, span_names=None) -> "Trace":
        """``span_names``: the host span names to keep (all when None)."""
        ops: dict[int, list] = defaultdict(list)
        spans = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        s = int(ev.start_ns)
                        text = ev.name
                        op = (op_name(text), s, s + int(ev.duration_ns))
                        if "custom-call" in text:
                            op += (text,)
                        ops[int(m.group(1))].append(op)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if span_names is None or ev.name in span_names:
                            s = int(ev.start_ns)
                            spans.append((ev.name, s, s + int(ev.duration_ns)))
        return cls(dict(ops), spans)

    # -- device time -----------------------------------------------------

    def busy_intervals(self, device: int) -> list[tuple[int, int]]:
        """Union of the op intervals of one chip, merged and sorted."""
        out: list[list[int]] = []
        for _, s, e, *_ in self.ops.get(device, []):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def busy_s(self, devices) -> float:
        """Seconds in which some op ran, averaged over ``devices``."""
        tot = [sum(e - s for s, e in self.busy_intervals(d)) for d in devices]
        return sum(tot) / len(tot) / 1e9 if tot else 0.0

    def op_seconds(self, devices, pattern: str | None = None,
                   text: str | None = None) -> dict[str, float]:
        """Summed device seconds by op name (averaged over ``devices``) of
        the leaf ops whose name matches ``pattern`` and, where ``text`` is
        given, whose HLO text (custom calls only) matches it."""
        rx = re.compile(pattern) if pattern else None
        tx = re.compile(text) if text else None
        acc: dict[str, float] = defaultdict(float)
        for d in devices:
            for name, s, e, *rest in self.ops.get(d, []):
                if CONTAINERS.match(name):
                    continue
                if rx is not None and not rx.search(name):
                    continue
                if tx is not None and not (rest and tx.search(rest[0])):
                    continue
                acc[name] += (e - s) / 1e9 / len(devices)
        return dict(acc)

    def kernel_seconds(self, devices, pattern: str | None = None,
                       text: str | None = None) -> float:
        return sum(self.op_seconds(devices, pattern, text).values())

    def top_ops(self, devices, n: int = 10) -> list[list]:
        items = sorted(self.op_seconds(devices).items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in items[:n]]

    # -- idle gaps ---------------------------------------------------------

    def idle_gaps(self, device: int) -> list[tuple[int, int]]:
        b = self.busy_intervals(device)
        return [(b[i][1], b[i + 1][0]) for i in range(len(b) - 1)
                if b[i + 1][0] > b[i][1]]

    def host_activity(self, t: int) -> str:
        """The innermost benchmark span covering host time ``t``."""
        best, best_len = "host: no span", None
        for name, s, e in self.spans:
            if s > t:
                break
            if e >= t and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
        return best

    def longest_gaps(self, device: int, n: int = 10) -> list[list]:
        """The n longest idle gaps of one chip, each named by what the host
        was doing at its midpoint: [[name, seconds], ...]."""
        gaps = sorted(self.idle_gaps(device), key=lambda g: g[0] - g[1])[:n]
        return [[self.host_activity((s + e) // 2), (e - s) / 1e9]
                for s, e in gaps]
