"""A tiny serve cell for CPU tests, written as new files into a copy of
the benchmark: a configuration, a traffic mix and a per-layer metric that
``BENCHMARK.json`` names, found by the harness by name alone."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

MODEL = {"hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
         "num_hidden_layers": 4, "vocab_size": 512, "rms_norm_eps": 1e-6,
         "rope_theta": 10000.0}

CONFIG = {"name": "tiny-split-serve", "kind": "serve",
          "source": "test configuration", "model": MODEL, "cut": 2,
          "dtype": "float32",
          "link": {"spec": "c3sl:R=4|int8", "R": 4, "key_seed": 0},
          "engine": {"num_slots": 8, "max_len": 128, "chunk_size": 32,
                     "sync_every": 4, "page_size": 16, "num_pages": 32, "kv_layout": "paged",
                     "kv_read": "gather", "prefill_mode": "chunked"},
          # on the CPU the program's float32 is exact float32, so its gaps
          # read 0; the control (int8 weights, bfloat16 products) reads
          # 1e-4 and more
          "check": {"rows": 24, "min_tokens": 20,
                    "control": {"weights": "int8", "dtype": "bfloat16",
                                "precision": "default"},
                    "limits": {"mean_gap": 1e-6, "widest_gap": 1e-5}}}

MIX = {"kind": "closed_loop", "clients": 8, "tenants": 2,
       "requests_per_client": 64,
       "prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 96},
       "output": {"median": 12, "sigma": 0.4, "min": 4, "max": 24}}

METRIC = '''"""Requests the window served per second (a test metric)."""


def read(ctx):
    s = ctx["served"]
    n = sum(1 for r in s.records if r["err"] is None and s.t0 <= r["t_done"] < s.t1)
    return n / (s.t1 - s.t0) if n else None
'''


def make_root(tmp: Path) -> Path:
    """A checkout-like root in ``tmp``: a copy of the benchmark plus the
    tiny cell's new files, and a BENCHMARK.json naming them."""
    root = tmp / "root"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "chipbench" / "configs" / "tiny-split-serve.json").write_text(
        json.dumps(CONFIG))
    (root / "chipbench" / "traffic" / "tiny_mix.json").write_text(json.dumps(MIX))
    (root / "chipbench" / "metrics" / "served_requests_per_s.py").write_text(METRIC)
    spec = {"command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
            "run_seconds": 2,
            "configs": [{"name": "tiny-split-serve", "source": "test",
                         "file": "chipbench/configs/tiny-split-serve.json",
                         "reduced": [], "why": "test"}],
            "workloads": [{"name": "tiny.mix", "config": "tiny-split-serve",
                           "traffic": "tiny_mix", "chips": 1, "why": "test"}],
            "end_to_end": [
                {"name": "gen_tokens_per_s", "unit": "tokens/s",
                 "better": "higher", "bound": 0.1, "source": "host_clock"},
                {"name": "setup_s", "unit": "s", "better": "lower",
                 "bound": 0.25, "source": "host_clock"}],
            "per_layer": [
                {"name": "served_requests_per_s", "unit": "1/s",
                 "better": "higher", "source": "host_clock", "layer": "test",
                 "moves": "gen_tokens_per_s"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def cpu_devices(chips: int):
    import jax
    return jax.devices()[:chips]


CHILD = """
import json, sys
sys.path[:0] = [{repo!r}, {src!r}]
from pathlib import Path
{patch}
from chipbench import run
from chipbench.tests import tiny
res = run.run({argv!r}, root=Path({root!r}), devices_for=tiny.cpu_devices,
              extra={extra!r})
res.pop("per_layer_ctx", None)
print("RESULT " + json.dumps(res, default=str))
"""


def run_in_child(tmp: Path, *, seed: int, trace: int = 0, seconds: float = 2.0,
                 patch: str = "", extra: dict | None = None) -> dict:
    """One run of the tiny cell in a CPU-only child process (JAX settings
    the harness makes stay out of the test process), optionally with
    ``patch`` (code) run before the harness.  Returns the result object."""
    import os
    import subprocess
    import sys
    root = tmp / "root"
    if not root.exists():
        make_root(tmp)
    if trace:
        peaks = json.loads((root / "chipbench" / "peaks.json").read_text())
        peaks["devices"]["cpu"] = {"bf16_flops": 1e12, "int8_ops": 1e12,
                                   "hbm_bytes_per_s": 1e11, "hbm_bytes": 2 ** 34}
        (root / "chipbench" / "peaks.json").write_text(json.dumps(peaks))
    repo = BENCH.parent
    code = CHILD.format(repo=str(repo), src=str(repo / "src"), patch=patch,
                        root=str(root), extra=extra or {},
                        argv=["--workload", "tiny.mix", "--seed", str(seed),
                              "--seconds", str(seconds), "--trace", str(trace)])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tmp),
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    if proc.returncode != 0 or not lines:
        raise AssertionError(f"tiny run failed ({proc.returncode}):\n"
                             f"{proc.stderr[-4000:]}")
    return json.loads(lines[-1][len("RESULT "):])
