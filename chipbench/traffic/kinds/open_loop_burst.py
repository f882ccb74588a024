"""Open loop with bursts: requests are due on a schedule whether or not
earlier ones have finished, as independent users send them.

Parameters: ``rate_rps`` (mean arrivals per second), ``burst``
({"factor", "period_s", "on_share"}: in the first ``on_share`` of every
period arrivals come at ``factor`` x the rate, and the rest of the period
at the rate that keeps the mean), ``clients`` (connections; requests are
dealt to them in turn), ``tenants``, ``warmup_s`` (the window opens this
long after the first arrival is due), and ``prompt`` / ``output``
log-normal length distributions.

Arrival times are the same set for every seed: unit-rate exponential
gaps at stratified quantiles, in an order the seed picks, mapped through
the burst profile's cumulative intensity.
"""
from __future__ import annotations

import math

from chipbench.traffic import generator as gen


def _intensity(mix):
    b = mix["burst"]
    rate, P = mix["rate_rps"], b["period_s"]
    on = b["on_share"] * P
    hi = b["factor"] * rate
    lo = rate * (1 - b["on_share"] * b["factor"]) / (1 - b["on_share"])
    if lo < 0:
        raise ValueError("burst factor x on_share must be <= 1")

    def cum(t):                       # expected arrivals in [0, t)
        k, r = divmod(t, P)
        return k * rate * P + (hi * r if r < on else hi * on + lo * (r - on))

    def inv(x):                       # the t at which cum(t) == x
        k, r = divmod(x, rate * P)
        t = k * P
        return t + (r / hi if r < hi * on else on + (r - hi * on) / lo)

    return cum, inv


def plan(mix: dict, rng, *, horizon_s: float) -> dict:
    cum, inv = _intensity(mix)
    n = int(math.floor(cum(horizon_s)))
    gaps = [-math.log(1 - (j + 0.5) / (n + 1)) for j in range(n + 1)]
    gaps = [gaps[i] for i in rng.permutation(n + 1)]
    scale = cum(horizon_s) / sum(gaps)
    prompts = gen.lognormal_quantiles(mix["prompt"], n)
    outputs = gen.lognormal_quantiles(mix["output"], n)
    prompts = [prompts[i] for i in rng.permutation(n)]
    outputs = [outputs[i] for i in rng.permutation(n)]
    C = mix["clients"]
    clients = [{"tenant": f"tenant-{c % mix['tenants']}", "requests": []}
               for c in range(C)]
    x = 0.0
    for k in range(n):
        x += gaps[k] * scale
        clients[k % C]["requests"].append(
            {"prompt_len": prompts[k], "max_new": outputs[k], "due": inv(x)})
    return {"loop": "open", "warmup_s": mix["warmup_s"], "clients": clients}
