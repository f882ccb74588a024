"""The one traffic generator: a mix is a data file, ``traffic/<name>.json``,
whose ``kind`` names a plan maker in ``traffic/kinds/<kind>.py``.

A plan maker returns the requests of a run from the mix's parameters and
the seed: for each client its tenant and requests (prompt length, output
length and, in an open loop, the second at which it is due).  Prompt
token ids are drawn here, per request, from the seed.

Lengths are quantiles of the mix's distribution, not draws: every seed
gets the same set of sizes (and an open loop the same set of gaps), only
in another order, so seeds change which request meets which and not how
much work a run holds.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent


def load(name: str, traffic_dir: Path = TRAFFIC_DIR) -> dict:
    return json.loads((traffic_dir / f"{name}.json").read_text())


def plan(mix: dict, seed: int, *, vocab: int, horizon_s: float,
         traffic_dir: Path = TRAFFIC_DIR) -> dict:
    """The run's plan: {"loop": "closed" | "open", "warmup_s": ...,
    "clients": [{"tenant", "requests": [{"prompt_len", "max_new"[, "due"]}]}]}."""
    import importlib.util
    path = traffic_dir / "kinds" / f"{mix['kind']}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_kind_{mix['kind']}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.plan(mix, rng(seed, 0), horizon_s=horizon_s)
    out["vocab"] = vocab
    return out


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...), for any seed size."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def prompt_tokens(seed: int, client: int, index: int, length: int,
                  vocab: int) -> list[int]:
    """Token ids of one prompt: uniform over [1, vocab)."""
    r = rng(seed, 1, client, index)
    return [int(t) for t in r.integers(1, vocab, length)]


def lognormal_quantiles(dist: dict, n: int, offset: float = 0.5) -> list[int]:
    """n stratified quantiles of a log-normal (median, sigma), at
    probabilities (k + offset) / n, clipped to [min, max] and rounded: the
    same set for every seed."""
    nd = NormalDist()
    mu = math.log(dist["median"])
    offset = min(max(offset, 1e-3), 1 - 1e-3)
    vals = [math.exp(mu + dist["sigma"] * nd.inv_cdf((k + offset) / n))
            for k in range(n)]
    return [int(min(max(round(v), dist["min"]), dist["max"])) for v in vals]
