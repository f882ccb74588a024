"""Closed loop: each client sends its next request when its last one has
completed.  Callers that each wait for an answer (batch jobs working
through a queue of documents) make this load; a slower system is sent
less.

Parameters: ``clients``, ``tenants`` (clients are spread round-robin over
them), ``requests_per_client`` (more than a run can use), ``prompt`` and
``output`` log-normal length distributions ({"median", "sigma", "min",
"max"}).  The first request of every client is sent at once; the window
opens when that first wave has completed.
"""
from __future__ import annotations

from chipbench.traffic import generator as gen


def plan(mix: dict, rng, *, horizon_s: float) -> dict:
    """Round k holds every client's k-th request.  Each round's prompt and
    output lengths are the same stratified quantiles for every seed (the
    strata shift by a fixed offset from round to round); the seed only
    deals them out among the clients."""
    C, n = mix["clients"], mix["requests_per_client"]
    clients = [{"tenant": f"tenant-{c % mix['tenants']}", "requests": []}
               for c in range(C)]
    for k in range(n):
        offset = (k * 0.6180339887498949) % 1.0
        prompts = gen.lognormal_quantiles(mix["prompt"], C, offset)
        outputs = gen.lognormal_quantiles(mix["output"], C, offset)
        for c, (i, j) in enumerate(zip(rng.permutation(C), rng.permutation(C))):
            clients[c]["requests"].append({"prompt_len": prompts[i],
                                           "max_new": outputs[j]})
    return {"loop": "closed", "clients": clients}
