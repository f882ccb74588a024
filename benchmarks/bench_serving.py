"""Serving control-flow benchmark of the continuous-batching engine, and
paged vs contiguous KV cache.

Runs the engine on a prompt-heavy and a decode-heavy request mix at
several codec specs and writes ``BENCH_serving.json``.  Its wall clocks
come from whatever host runs it (CI runs it on a CPU), so they show that
each path runs and what it counts, never how fast the chip serves: the
chip benchmark is ``chipbench/``.  A third, mixed long/short-prompt
workload compares the paged KV cache (oversubscribed page pool) against
the contiguous per-slot strips on tokens/s, mean/max time-to-first-token,
and peak cache bytes — with and without prefill/decode interleaving.
A fourth, multi-tenant Poisson workload (a standard tenant's short
priority-0 stream plus a premium tenant's long priority-1 requests over
an oversubscribed page pool) compares slot preemption against FIFO
blocking on per-tenant TTFT p50/p99 and time-weighted pool utilization.
All timed sections run identically-seeded repeats and report the
min/mean/max tokens/s spread (full mode: 3 repeats; smoke: 1).
See benchmarks/README.md for the protocol and the JSON schema.

    PYTHONPATH=src python -m benchmarks.bench_serving [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import jax
import numpy as np

MIXES = {
    # name: (prompt_len, max_new_tokens) — prompt-heavy is where chunked
    # prefill pays off (O(L/C) dispatches instead of O(L)); decode-heavy
    # isolates the device-resident stepping + batched EOS fetches.
    "prompt_heavy": (64, 8),
    "decode_heavy": (8, 48),
}
SMOKE_MIXES = {"prompt_heavy": (16, 2), "decode_heavy": (4, 6)}

CODECS = ["none", "c3sl:R=4", "c3sl:R=4|int8"]
SMOKE_CODECS = ["none", "c3sl:R=2"]

# Mixed long/short workload for the paged-vs-contiguous comparison: requests
# alternate the two prompt lengths, so under the contiguous layout every
# short request still reserves a full max_len strip while the paged pool
# (sized below slots * max_len) only holds what each request can touch.
MIXED = {"long": (96, 16), "short": (8, 16), "n_each": 4}
SMOKE_MIXED = {"long": (12, 2), "short": (3, 2), "n_each": 2}

# Multi-tenant Poisson workload: a "standard" tenant streams short
# priority-0 requests while a "premium" tenant occasionally submits a
# long priority-1 request whose page footprint doesn't fit the
# oversubscribed pool alongside a full complement of standard slots.
# Under FIFO the premium head blocks admission while the pool drains;
# with preemption it evicts standard slots and is admitted immediately.
# Arrival times are in TICK units (deterministic given the seed), not
# wall-clock: per tenant, inter-arrival gaps ~ Exp(mean_gap) ticks.
# max_new spans several sync_every decode windows so requests stay
# resident across ticks — a request that finishes inside one tick can
# neither be observed occupying the pool nor be preempted.
MULTI_TENANT = {
    "standard": {"prompt_len": 8, "max_new": 32, "n": 16, "mean_gap": 3.0,
                 "priority": 0},
    "premium": {"prompt_len": 96, "max_new": 24, "n": 3, "mean_gap": 25.0,
                "priority": 1},
}
SMOKE_MULTI_TENANT = {
    "standard": {"prompt_len": 4, "max_new": 12, "n": 4, "mean_gap": 2.0,
                 "priority": 0},
    "premium": {"prompt_len": 20, "max_new": 8, "n": 1, "mean_gap": 8.0,
                "priority": 1},
}

# Speculative decoding sweep: decode-heavy on purpose — verify rounds ship
# ZERO forward bytes (the server replays the bottom stack from known token
# ids), so the per-generated-token wire cost is what k amortizes, and
# prompt prefill (unchanged by speculation) must not drown the signal.
# The sweep pins the criterion on the "copy" draft head (client-side, no
# feedback payload) and adds one "tied" row at k=4 to record the
# draft-codec feedback channel's acceptance/wire tradeoff.
SPEC_KS = (1, 2, 4, 8)
SPEC_MIX = {"prompt_len": 8, "max_new": 48}
SMOKE_SPEC_MIX = {"prompt_len": 4, "max_new": 24}
SPEC_CODECS = ["none", "c3sl:R=4|int8"]
SMOKE_SPEC_CODECS = ["none", "c3sl:R=2|int8"]


def _agg_reps(rows: list[dict]) -> dict:
    """Collapse repeated runs (identical pinned seeds -> identical token
    streams) into one row: mean wall/throughput plus min/max spread."""
    tok = {r["generated_tokens"] for r in rows}
    assert len(tok) == 1, f"pinned seeds but divergent outputs: {tok}"
    tps = [r["tokens_per_s"] for r in rows]
    out = dict(rows[0])
    out["wall_s"] = round(sum(r["wall_s"] for r in rows) / len(rows), 4)
    out["tokens_per_s"] = round(sum(tps) / len(tps), 1)
    out["tokens_per_s_min"] = min(tps)
    out["tokens_per_s_max"] = max(tps)
    out["repeats"] = len(rows)
    return out


def _build(smoke: bool):
    from repro.configs.base import get_config, reduced
    from repro.models import lm as lm_lib
    cfg = reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                  d_ff=256, vocab_size=256, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run_once(cfg, params, *, codec, prompt_len, max_new, requests,
              num_slots, max_len, chunk_size, sync_every, seed=0, reps=1):
    from repro.serving.engine import BatchedEngine, Request
    eng = BatchedEngine(params, cfg, num_slots=num_slots, max_len=max_len,
                        codec=codec, greedy=True, seed=seed,
                        chunk_size=chunk_size, sync_every=sync_every)

    def batch(n, uid0, rng):
        return [Request(uid=uid0 + i,
                        prompt=list(map(int, rng.randint(1, cfg.vocab_size,
                                                         prompt_len))),
                        max_new_tokens=max_new) for i in range(n)]

    # warmup: compile every program (prefill, fused step, reset) off the clock
    for r in batch(min(2, requests), 10_000, np.random.RandomState(seed + 99)):
        eng.submit(r)
    eng.run()
    eng.finished.clear()

    rows = []
    for rep in range(reps):
        # identical pinned seed every rep: same prompts, same token streams
        reqs = batch(requests, rep * 100_000,
                     np.random.RandomState(seed + 1))
        for r in reqs:
            eng.submit(r)
        t0 = time.time()
        done = list(eng.run())      # copy: run() returns eng.finished itself
        wall = time.time() - t0
        assert len(done) == requests, (len(done), requests)
        eng.finished.clear()
        generated = sum(len(r.out) for r in done)
        total = generated + requests * prompt_len
        rows.append({"wall_s": round(wall, 4),
                     "prompt_tokens": requests * prompt_len,
                     "generated_tokens": generated,
                     "tokens_per_s": round(total / wall, 1)})
    return _agg_reps(rows)


def _run_mixed(cfg, params, *, kv_layout, interleave, mixed, num_slots,
               max_len, page_size, num_pages, chunk_size, sync_every, seed=0,
               reps=1):
    """Mixed long/short runs; returns throughput, TTFT, and cache bytes
    aggregated over ``reps`` identically-seeded repeats."""
    from repro.serving.engine import BatchedEngine, Request
    eng = BatchedEngine(params, cfg, num_slots=num_slots, max_len=max_len,
                        greedy=True, seed=seed,
                        chunk_size=chunk_size, sync_every=sync_every,
                        kv_layout=kv_layout, page_size=page_size,
                        num_pages=num_pages if kv_layout == "paged" else None,
                        interleave=interleave)
    (llen, lnew), (slen, snew) = mixed["long"], mixed["short"]

    def batch(uid0, rng):
        reqs = []
        for i in range(mixed["n_each"]):
            for ln, mn in ((llen, lnew), (slen, snew)):
                reqs.append(Request(
                    uid=uid0 + len(reqs),
                    prompt=list(map(int, rng.randint(1, cfg.vocab_size, ln))),
                    max_new_tokens=mn))
        return reqs

    # warmup: compile off the clock
    for r in batch(10_000, np.random.RandomState(seed + 99))[:2]:
        eng.submit(r)
    eng.run()
    eng.finished.clear()

    rows = []
    for rep in range(reps):
        eng.stats = {k: 0 for k in eng.stats}    # count this rep only
        reqs = batch(rep * 100_000, np.random.RandomState(seed + 1))
        t0 = time.time()
        for r in reqs:
            eng.submit(r)
        done = list(eng.run())      # copy: run() returns eng.finished itself
        wall = time.time() - t0
        assert len(done) == len(reqs), (len(done), len(reqs))
        eng.finished.clear()
        generated = sum(len(r.out) for r in done)
        prompt_tokens = sum(len(r.prompt) for r in reqs)
        ttfts = [r.t_first - r.t_submit for r in done
                 if r.t_first is not None]
        rows.append({"wall_s": round(wall, 4),
                     "prompt_tokens": prompt_tokens,
                     "generated_tokens": generated,
                     "tokens_per_s": round((prompt_tokens + generated) / wall,
                                           1),
                     "ttft_mean_s": round(sum(ttfts) / len(ttfts), 4),
                     "ttft_max_s": round(max(ttfts), 4),
                     "peak_cache_bytes": eng.cache_bytes,
                     "dispatches": eng.stats["dispatches"]})
    out = _agg_reps(rows)
    out["ttft_mean_s"] = round(
        sum(r["ttft_mean_s"] for r in rows) / len(rows), 4)
    out["ttft_max_s"] = round(max(r["ttft_max_s"] for r in rows), 4)
    return out


def _run_multi_tenant(cfg, params, *, tenants, preemption, num_slots,
                      max_len, page_size, num_pages, chunk_size, sync_every,
                      seed=0):
    """Drive the engine tick-by-tick under a Poisson (per-tenant) arrival
    schedule; returns per-tenant TTFT percentiles and the time-weighted
    page-pool utilization.  The arrival schedule is identical for every
    ``preemption`` setting (same seed -> same ticks, prompts, priorities)."""
    from repro.serving.engine import BatchedEngine, Request
    eng = BatchedEngine(params, cfg, num_slots=num_slots, max_len=max_len,
                        greedy=True, seed=seed, chunk_size=chunk_size,
                        sync_every=sync_every,
                        kv_layout="paged", page_size=page_size,
                        num_pages=num_pages, preemption=preemption)

    rng = np.random.RandomState(seed + 1)
    schedule = []        # (arrival_tick, tenant, prompt, max_new, priority)
    for name in sorted(tenants):
        t = tenants[name]
        ticks = np.cumsum(rng.exponential(t["mean_gap"], t["n"]))
        for at in ticks:
            prompt = list(map(int, rng.randint(1, cfg.vocab_size,
                                               t["prompt_len"])))
            schedule.append((float(at), name, prompt, t["max_new"],
                             t["priority"]))
    schedule.sort(key=lambda s: s[0])

    # warmup: compile prefill/decode/reset programs off the clock (one
    # request per tenant shape; the preemption path reuses the same
    # programs, so nothing compiles mid-measurement)
    for uid, name in enumerate(sorted(tenants)):
        t = tenants[name]
        eng.submit(Request(uid=10_000 + uid,
                           prompt=[1] * t["prompt_len"],
                           max_new_tokens=t["max_new"]))
    eng.run()
    eng.finished.clear()
    eng.stats = {k: 0 for k in eng.stats}

    tenant_of = {}
    pending = [(at, name, Request(uid=uid, prompt=prompt, max_new_tokens=mn,
                                  priority=pr))
               for uid, (at, name, prompt, mn, pr) in enumerate(schedule)]
    for _, name, req in pending:
        tenant_of[req.uid] = name
    total = eng.paged.num_pages
    util_num = util_den = 0.0
    tick = done = 0
    t_start = time.time()
    while pending or eng.queue or eng.active:
        while pending and pending[0][0] <= tick:
            eng.submit(pending.pop(0)[2])
        t0 = time.time()
        moved = eng.tick()
        dt = time.time() - t0
        if moved:
            # time-weighted occupancy: what fraction of the page pool did
            # useful work while the engine was busy this tick
            util_num += dt * eng.pool_accounting()["in_use"] / total
            util_den += dt
        tick += 1
    wall = time.time() - t_start
    finished, eng.finished = list(eng.finished), []
    assert len(finished) == len(schedule), (len(finished), len(schedule))

    per_tenant = {}
    for req in finished:
        per_tenant.setdefault(tenant_of[req.uid], []).append(req)
    tenant_rows = {}
    for name, reqs in sorted(per_tenant.items()):
        ttfts = [r.t_first - r.t_submit for r in reqs
                 if r.t_first is not None]
        tenant_rows[name] = {
            "requests": len(reqs),
            "priority": tenants[name]["priority"],
            "generated_tokens": sum(len(r.out) for r in reqs),
            "evictions": sum(r.evictions for r in reqs),
            "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
            "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4),
            "ttft_max_s": round(max(ttfts), 4)}
    generated = sum(len(r.out) for r in finished)
    prompt_tokens = sum(len(r.prompt) for r in finished)
    return {"wall_s": round(wall, 4),
            "prompt_tokens": prompt_tokens,
            "generated_tokens": generated,
            "tokens_per_s": round((prompt_tokens + generated) / wall, 1),
            "pool_utilization": round(util_num / max(util_den, 1e-9), 3),
            "evictions": eng.stats["evictions"],
            "eos_early_exits": eng.stats["eos_early_exits"],
            "ticks": tick,
            "tenants": tenant_rows}


def _run_spec(cfg, params, *, codec, spec_decode, prompt_len, max_new,
              requests, num_slots, max_len, chunk_size, sync_every, seed=0):
    """One speculative (or k=1 vanilla) run with exact wire accounting:
    stats are zeroed after warmup so the measured totals cover exactly the
    timed requests, then the engine's per-channel counters are
    cross-checked against an independent recomputation."""
    from repro.serving.engine import BatchedEngine, Request
    eng = BatchedEngine(params, cfg, num_slots=num_slots, max_len=max_len,
                        codec=codec, greedy=True, seed=seed,
                        chunk_size=chunk_size,
                        sync_every=sync_every, spec_decode=spec_decode)

    def batch(n, uid0, rng):
        return [Request(uid=uid0 + i,
                        prompt=list(map(int, rng.randint(1, cfg.vocab_size,
                                                         prompt_len))),
                        max_new_tokens=max_new) for i in range(n)]

    for r in batch(min(2, requests), 10_000, np.random.RandomState(seed + 99)):
        eng.submit(r)
    eng.run()
    eng.finished.clear()
    eng.stats = {k: 0 for k in eng.stats}
    eng.r_served.clear()
    eng.k_served.clear()
    eng._tokens_decoded = 0

    reqs = batch(requests, 0, np.random.RandomState(seed + 1))
    for r in reqs:
        eng.submit(r)
    t0 = time.time()
    done = list(eng.run())
    wall = time.time() - t0
    assert len(done) == requests, (len(done), requests)
    eng.finished.clear()
    done.sort(key=lambda r: r.uid)
    outputs = [r.out for r in done]
    generated = sum(len(o) for o in outputs)

    wpt = eng.wire_per_token()
    # satellite cross-check: the per-token metric must be consistent with
    # the engine's raw channel counters AND with an independent
    # recomputation from the served round schedule
    assert wpt["wire_bytes_fwd"] == eng.stats["payload_wire_bytes"], wpt
    assert wpt["generated_tokens"] == generated, (wpt, generated)
    if spec_decode is not None:
        draft_expect = sum(rounds * eng._draft_round_wire_bytes(kk)
                           for kk, rounds in eng.k_served.items())
        assert wpt["wire_bytes_draft"] == draft_expect, \
            (wpt, dict(eng.k_served))
    else:
        assert wpt["wire_bytes_draft"] == 0, wpt

    acc = eng.stats["spec_accepted"]
    rej = eng.stats["spec_rejected"]
    row = {"wall_s": round(wall, 4),
           "prompt_tokens": requests * prompt_len,
           "generated_tokens": generated,
           "tokens_per_s": round((generated + requests * prompt_len) / wall,
                                 1),
           "spec_rounds": eng.stats["spec_rounds"],
           "spec_rollbacks": eng.stats["spec_rollbacks"],
           "acceptance_rate": (round(acc / (acc + rej), 3)
                               if acc + rej else None),
           "wire_bytes_fwd": wpt["wire_bytes_fwd"],
           "wire_bytes_draft": wpt["wire_bytes_draft"],
           "wire_bytes_per_token": round(wpt["wire_bytes_per_token"], 2)}
    return row, outputs


def bench_spec(cfg, params, smoke, chunk_size, sync_every, results):
    """Speculative decoding: k-sweep per codec, greedy outputs pinned
    bit-identical to the k=1 vanilla run, wire bytes per generated token
    vs the vanilla baseline (the ISSUE criterion: <= 0.5x at k=4 on the
    codec workload)."""
    from repro.serving.spec import SpecConfig
    mix = SMOKE_SPEC_MIX if smoke else SPEC_MIX
    codecs = SMOKE_SPEC_CODECS if smoke else SPEC_CODECS
    requests = 2 if smoke else 8
    num_slots = 2 if smoke else 4
    max_len = 32 if smoke else 128
    common = dict(prompt_len=mix["prompt_len"], max_new=mix["max_new"],
                  requests=requests, num_slots=num_slots, max_len=max_len,
                  chunk_size=chunk_size, sync_every=sync_every)
    for codec in codecs:
        ref_out = None
        base_wpt = None
        runs = [(k, "copy", None) for k in SPEC_KS]
        if codec != "none":
            # the tied head pays the draft-codec feedback payload in
            # exchange for model-informed drafts — recorded, not pinned
            runs.append((4, "tied", codec))
        for k, head, draft in runs:
            spec_cfg = (None if k == 1 else
                        SpecConfig(k=k, draft=draft, draft_head=head))
            r, outputs = _run_spec(cfg, params, codec=codec,
                                   spec_decode=spec_cfg, **common)
            if ref_out is None:
                ref_out = outputs
            else:
                assert outputs == ref_out, (
                    f"speculative outputs diverged from vanilla decode at "
                    f"codec={codec} k={k} head={head}")
            row = {"mix": "spec_decode", "codec": codec,
                   "spec_k": k, "draft_head": head if k > 1 else None,
                   "draft_codec": draft, "chunk_size": chunk_size,
                   "sync_every": sync_every, "requests": requests,
                   "num_slots": num_slots, **r}
            if k == 1:
                base_wpt = r["wire_bytes_per_token"]
            elif base_wpt:
                ratio = round(r["wire_bytes_per_token"] / base_wpt, 3)
                row["wire_per_token_vs_k1"] = ratio
                if k == 4 and head == "copy" and codec != "none":
                    row["meets_criteria"] = ratio <= 0.5
            results.append(row)
            rate = r["acceptance_rate"]
            print(f"spec_decode codec={codec:16s} k={k} head={head:4s} "
                  f"{r['tokens_per_s']:8.1f} tok/s  "
                  f"accept {rate if rate is not None else '-':>5}  "
                  f"wire {r['wire_bytes_per_token']:7.2f} B/token"
                  + (f"  ({row['wire_per_token_vs_k1']:.3f}x vs k=1)"
                     if "wire_per_token_vs_k1" in row else ""), flush=True)
    return results


def bench_multi_tenant(cfg, params, smoke, chunk_size, sync_every, results):
    """Preemption on vs off under the oversubscribed multi-tenant mix."""
    tenants = SMOKE_MULTI_TENANT if smoke else MULTI_TENANT
    num_slots = 2 if smoke else 4
    max_len = 32 if smoke else 128
    page_size = 8 if smoke else 16
    # pool sized so a full complement of standard slots + one premium
    # request oversubscribes it: premium needs pages the standards hold
    num_pages = 4 if smoke else 10
    base = None
    for preemption in (False, True):
        r = _run_multi_tenant(cfg, params, tenants=tenants,
                              preemption=preemption, num_slots=num_slots,
                              max_len=max_len, page_size=page_size,
                              num_pages=num_pages, chunk_size=chunk_size,
                              sync_every=sync_every)
        row = {"mix": "multi_tenant", "codec": "none",
               "kv_layout": "paged", "preemption": preemption,
               "page_size": page_size, "num_pages": num_pages,
               "chunk_size": chunk_size, "sync_every": sync_every,
               "requests": sum(t["n"] for t in tenants.values()),
               "num_slots": num_slots, **r}
        if base is None:
            base = r
        else:
            row["utilization_vs_fifo"] = round(
                r["pool_utilization"] / max(base["pool_utilization"], 1e-9),
                2)
            prem = [n for n, t in tenants.items() if t["priority"] > 0][0]
            row["premium_ttft_p99_vs_fifo"] = round(
                r["tenants"][prem]["ttft_p99_s"]
                / max(base["tenants"][prem]["ttft_p99_s"], 1e-9), 3)
        results.append(row)
        for name, t in r["tenants"].items():
            print(f"multi_tenant preempt={str(preemption):5s} "
                  f"{name:9s} ttft p50 {t['ttft_p50_s']*1e3:8.1f}ms "
                  f"p99 {t['ttft_p99_s']*1e3:8.1f}ms "
                  f"evictions {t['evictions']}", flush=True)
        print(f"multi_tenant preempt={str(preemption):5s} pool util "
              f"{r['pool_utilization']:.3f} "
              f"({r['tokens_per_s']:.1f} tok/s, "
              f"{r['evictions']} evictions)", flush=True)
    return results


def bench_mixed(cfg, params, smoke, chunk_size, sync_every, results, reps=1):
    """Paged vs contiguous (and the interleave knob) on the mixed workload."""
    mixed = SMOKE_MIXED if smoke else MIXED
    num_slots = 2 if smoke else 4
    max_len = 32 if smoke else 128
    page_size = 8 if smoke else 16
    # pool sized to the worst-case CONCURRENT reservation of the alternating
    # admission order (full: 2 long + 2 short = 7+7+2+2 pages), well under
    # the contiguous equivalent of slots * max_len/page_size pages
    num_pages = 3 if smoke else 18
    base = None
    for kv_layout, interleave in (("contiguous", 0), ("paged", 0),
                                  ("paged", 2)):
        # identically-seeded repeats (full mode): wall-clock on shared CPU
        # runners is noisy and the layouts execute identical token streams,
        # so report the spread (min/mean/max), not a lucky best-of
        r = _run_mixed(cfg, params, kv_layout=kv_layout,
                       interleave=interleave, mixed=mixed,
                       num_slots=num_slots, max_len=max_len,
                       page_size=page_size, num_pages=num_pages,
                       chunk_size=chunk_size, sync_every=sync_every,
                       reps=reps)
        row = {"mix": "mixed_long_short", "codec": "none",
               "kv_layout": kv_layout, "interleave": interleave,
               "page_size": page_size if kv_layout == "paged" else None,
               "num_pages": num_pages if kv_layout == "paged" else None,
               "chunk_size": chunk_size, "sync_every": sync_every,
               "requests": 2 * mixed["n_each"], "num_slots": num_slots, **r}
        if base is None:
            base = r
        else:
            row["cache_bytes_vs_contiguous"] = round(
                r["peak_cache_bytes"] / base["peak_cache_bytes"], 3)
            row["speedup_vs_contiguous"] = round(
                r["tokens_per_s"] / base["tokens_per_s"], 2)
        results.append(row)
        print(f"mixed_long_short kv={kv_layout:10s} il={interleave} "
              f"{r['tokens_per_s']:8.1f} tok/s  ttft {r['ttft_mean_s']*1e3:7.1f}ms "
              f"(max {r['ttft_max_s']*1e3:7.1f}ms)  "
              f"cache {r['peak_cache_bytes']/1e6:6.2f}MB", flush=True)
    return results


def main(smoke: bool = False, out: str = "BENCH_serving.json",
         chunk_size: int = 16):
    cfg, params = _build(smoke)
    mixes = SMOKE_MIXES if smoke else MIXES
    codecs = SMOKE_CODECS if smoke else CODECS
    requests = 2 if smoke else 8
    num_slots = 2 if smoke else 4
    max_len = 32 if smoke else 128
    sync_every = 4 if smoke else 8
    # identically-seeded repeats: report the wall-clock spread, not one draw
    reps = 1 if smoke else 3

    results = []
    for mix, (prompt_len, max_new) in mixes.items():
        for spec in codecs:
            r = _run_once(cfg, params, codec=spec, prompt_len=prompt_len,
                          max_new=max_new, requests=requests,
                          num_slots=num_slots, max_len=max_len,
                          chunk_size=chunk_size, sync_every=sync_every,
                          reps=reps)
            results.append({"mix": mix, "codec": spec,
                            "chunk_size": chunk_size,
                            "sync_every": sync_every,
                            "requests": requests, "num_slots": num_slots,
                            **r})
            print(f"{mix:13s} codec={spec:16s} "
                  f"{r['tokens_per_s']:8.1f} tok/s", flush=True)

    bench_mixed(cfg, params, smoke, chunk_size, sync_every, results,
                reps=reps)
    bench_multi_tenant(cfg, params, smoke, chunk_size, sync_every, results)
    bench_spec(cfg, params, smoke, chunk_size, sync_every, results)

    payload = {
        "protocol": {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "host": platform.platform(),
            "device": jax.devices()[0].platform,
            "jax": jax.__version__,
            "smoke": smoke,
        },
        "arch": {"name": cfg.name, "num_layers": cfg.num_layers,
                 "d_model": cfg.d_model, "d_ff": cfg.d_ff,
                 "vocab_size": cfg.vocab_size},
        "mixes": {**{k: {"prompt_len": v[0], "max_new_tokens": v[1]}
                     for k, v in mixes.items()},
                  "spec_decode": {
                      "prompt_len": (SMOKE_SPEC_MIX if smoke
                                     else SPEC_MIX)["prompt_len"],
                      "max_new_tokens": (SMOKE_SPEC_MIX if smoke
                                         else SPEC_MIX)["max_new"]}},
        "results": results,
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (seconds, not minutes)")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--chunk-size", type=int, default=16)
    args = ap.parse_args()
    main(smoke=args.smoke, out=args.out, chunk_size=args.chunk_size)
