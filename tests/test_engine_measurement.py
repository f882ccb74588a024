"""The engine's and the front door's own measurement: counters in
``engine.stats`` (exact values under an injected clock), the C3-SL
dispatch record, and the host spans a profiler trace of a front-door run
holds, nested as the code nests them."""
import asyncio
import glob

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs.base import get_config, reduced
from repro.frontdoor import FrontDoorClient, FrontDoorServer
from repro.models import lm as lm_lib
from repro.serving.engine import BatchedEngine, Request


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                  d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    return cfg, lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _prompt(rng, n):
    return [int(t) for t in rng.randint(1, 128, n)]


def test_counters_and_record_exact(setup):
    """Two slots, 4 pages of 4 positions.  A (prompt 6, 2 new: 2 pages)
    is admitted at t=1; B (prompt 10, 6 new: 4 pages) waits for A's
    pages until t=3, then prefills in a ragged two-chunk pass (8 + 2)."""
    cfg, params = setup
    eng = BatchedEngine(params, cfg, num_slots=2, max_len=32, chunk_size=8,
                        sync_every=8, greedy=True, seed=0,
                        kv_layout="paged", page_size=4, num_pages=4)
    clock = eng.clock = FakeClock()
    record = eng.record_dispatches()
    rng = np.random.RandomState(5)
    a = Request(uid=10, prompt=_prompt(rng, 6), max_new_tokens=2)
    b = Request(uid=11, prompt=_prompt(rng, 10), max_new_tokens=6)
    eng.submit(a)
    eng.submit(b)
    clock.t = 1.0
    eng._boundary()                       # A admitted; B blocked on pages
    assert eng.stats["admitted"] == 1 and eng.stats["queue_wait_s"] == 1.0
    eng._prefill_one_chunk()
    clock.t = 2.0
    # the pool starves B, so the window exits when A finishes, and A is
    # retired at that read: its first token was first read back then
    assert eng._decode_window(8) == 1
    assert a.t_first == 2.0 and a.done
    clock.t = 3.0
    eng._boundary()                       # B admitted after 3 s
    eng._prefill_one_chunk()
    eng._prefill_one_chunk()
    clock.t = 4.0
    eng._boundary()                       # B's prompt read back: 3 pages
    assert b.t_first == 4.0
    assert eng._decode_window(8) == 5
    clock.t = 6.0
    eng._boundary()
    assert b.done and not eng.queue and not eng.active
    s = eng.stats
    assert (s["admitted"], s["queue_wait_s"]) == (2, 1.0 + 3.0)
    # each chunk computes slot 0's group alone (no codec: one slot a group)
    assert (s["prefill_tokens"], s["prefill_rows"]) == (6 + 8 + 2, 3 * 1 * 8)
    # page-seconds between reads at t = 1, 3, 4, 6, each interval weighted
    # by the pages at its start: written 0, 0, 3; reserved 2, 4, 4
    assert s["kv_written_page_s"] == 2 * 3
    assert s["kv_reserved_page_s"] == 2 * 2 + 1 * 4 + 2 * 4
    assert s["kv_pool_page_s"] == 5 * 4
    # both requests sat in slot 0 (A retired before B's admission)
    assert [(k, rows) for _, k, rows in record] == [
        ("P", [(0, 10, 0, 6)]),
        ("D", [(0, 10, 6)]),
        ("P", [(0, 11, 0, 8)]),
        ("P", [(0, 11, 8, 2)]),
    ] + [("D", [(0, 11, p)]) for p in range(10, 15)]
    assert eng.record_dispatches(False) is None
    assert eng.dispatch_record is None


def test_record_is_off_by_default_and_counts_every_slot(setup):
    cfg, params = setup
    eng = BatchedEngine(params, cfg, num_slots=2, max_len=32, chunk_size=8,
                        sync_every=4, greedy=True, seed=0)
    assert eng.dispatch_record is None
    rng = np.random.RandomState(1)
    for u in range(3):
        eng.submit(Request(uid=u, prompt=_prompt(rng, 5 + 4 * u),
                           max_new_tokens=5))
    done = eng.run()
    assert len(done) == 3
    s = eng.stats
    assert s["admitted"] == 3 and s["queue_wait_s"] > 0
    assert s["prefill_tokens"] == 5 + 9 + 13
    # chunks: uids 0 and 1 (5 + 8 tokens, both slots), uid 1's last token,
    # then uid 2's 8 + 5 alone; a chunk computes only its prefilling slots
    assert s["prefill_chunks"] == 4
    assert s["prefill_rows"] == (2 + 1 + 1 + 1) * 8
    # a contiguous cache has no page pool to integrate
    assert s["kv_pool_page_s"] == 0.0


# every span the program writes, and the span each must lie inside
PARENTS = {
    "frontdoor.pump": None,
    "frontdoor.submit": None,
    "frontdoor.stream_tokens": ("frontdoor.pump",),
    "frontdoor.deliver": ("frontdoor.pump",),
    "engine.tick": ("frontdoor.pump",),
    "engine.boundary": ("engine.tick",),
    "engine.prefill_chunk": ("engine.tick",),
    "engine.decode_window": ("engine.tick",),
    "engine.device": ("engine.boundary", "engine.prefill_chunk",
                      "engine.decode_window"),
}


def _host_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PARENTS:
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns),
                                  dict(ev.stats)))
    return spans


def test_front_door_trace_holds_every_span_nested(setup, tmp_path):
    cfg, params = setup
    rng = np.random.RandomState(4)
    prompts = [_prompt(rng, 11 + 3 * i) for i in range(3)]

    async def go():
        eng = BatchedEngine(params, cfg, num_slots=2, max_len=48,
                            chunk_size=8, sync_every=4, greedy=True, seed=0,
                            kv_layout="paged", page_size=8, num_pages=12)
        server = FrontDoorServer(eng, auto_tick=False)
        host, port = await server.start()
        client = await FrontDoorClient.open(host, port, tenant="t0",
                                            codec="none")
        try:
            rids = [await client.submit(p, max_new=6) for p in prompts]
            await server.drain()
            outs = [await client.result(rid) for rid in rids]
            stats = await client.stats()
        finally:
            await client.close()
            await server.stop(drain=False)
        return outs, stats

    jax.profiler.start_trace(str(tmp_path))
    try:
        outs, stats = asyncio.run(go())
    finally:
        jax.profiler.stop_trace()
    assert all(len(o["tokens"]) == 6 for o in outs)
    # the STATS RPC carries the engine's counters
    eng_stats = stats["engine"]
    assert eng_stats["admitted"] == 3
    assert eng_stats["prefill_tokens"] == sum(map(len, prompts))
    assert eng_stats["kv_pool_page_s"] > 0

    spans = _host_spans(tmp_path)
    assert {n for n, *_ in spans} == set(PARENTS)
    for name, s, e, _ in spans:
        parents = PARENTS[name]
        if parents is None:
            continue
        assert any(p in parents and ps <= s and e <= pe
                   for p, ps, pe, _ in spans), (name, s, e)
    submits = [st for n, _, _, st in spans if n == "frontdoor.submit"]
    assert sorted(st["uid"] for st in submits) == [0, 1, 2]
