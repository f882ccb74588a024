"""What every run of the benchmark shares: the spec, the device, seeds,
compile counting, per-layer metric readers and the result line.

Nothing here imports the program under test.  ``jax`` is imported lazily
so that a directory holding only the benchmark still fails with a clear
message instead of an import-time crash in a child.
"""
from __future__ import annotations

import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class RunError(Exception):
    """A run that cannot produce a result (no chip, bad spec)."""


# ---------------------------------------------------------------------------
# spec: BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise RunError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise RunError(f"workload {name!r} is not in BENCHMARK.json")


def load_config(spec: dict, name: str, root: Path = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise RunError(f"configuration {name!r} is not in BENCHMARK.json")


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (files are found by the name
    BENCHMARK.json gives, never by an edit to a table)."""
    if not path.is_file():
        raise RunError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` ("end_to_end" | "per_layer") this cell
    reports: those that list it, or list no cells at all."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_per_layer(spec: dict, cell: str, ctx: dict,
                   bench_dir: Path = BENCH_DIR) -> dict:
    """Run each per-layer metric's reader (``metrics/<name>.py``) on the
    reduced trace and counters; a reader that finds nothing returns None
    and the metric is left out."""
    out = {}
    for m in cell_metrics(spec, cell, "per_layer"):
        mod = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                          f"chipbench_metric_{m['name']}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A JAX PRNG key for any non-negative seed, also past 32 bits."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def check_devices(chips: int):
    """The devices a cell runs on: it needs a TPU and ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise RunError(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_info(devices) -> dict:
    import jax
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def load_peaks(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise RunError(f"device kind {kind!r} has no entry in peaks.json")
    return table[kind]


class CompileCounter:
    """Counts JAX traces and backend compiles (and their seconds), and
    persistent-cache hits and misses, from JAX's monitoring events."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.traces = self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1
        else:
            if event != self.LOWER:
                return
        self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def count(self) -> int:
        return self.traces + self.compiles


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile (0 < q < 100) by the inclusive method of
    ``statistics.quantiles``; the value itself for a single sample."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    if len(vals) == 1:
        return vals[0]
    cuts = statistics.quantiles(vals, n=1000, method="inclusive")
    return cuts[int(round(q * 10)) - 1]


# ---------------------------------------------------------------------------
# output
# ---------------------------------------------------------------------------

def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Clock:
    """Seconds since the process started the benchmark."""

    def __init__(self):
        self.t0 = time.monotonic()

    def __call__(self) -> float:
        return time.monotonic() - self.t0


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: list[dict], breakdown=None) -> str:
    """The last line of standard output.  ``checks`` (each number compared
    beside its limit) comes last, as its own key."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: list[dict]) -> None:
    """Each number compared, beside its limit, as the last lines of
    standard error."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}, "
              f"{'ok' if c['ok'] else 'FAILED'})", file=sys.stderr, flush=True)
