"""Slot preemption/eviction and early page release.

Pins the engine-side half of the front door's QoS story:

- an evicted request re-prefills prompt + emitted tokens and resumes to a
  greedy output BIT-IDENTICAL to an uninterrupted run;
- the page-pool accounting invariant (free + in_use == total) holds
  across evict/realloc cycles;
- equal-priority work is never preempted (``preemption=False`` and the
  default priority keep the seed's strict FIFO);
- a slot retiring at the decode window's EOS early exit frees its WHOLE
  reservation at that host sync — before any admit/retire boundary —
  with outputs captured at their actual emitted length.
"""
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models import lm as lm_lib
from repro.serving.engine import BatchedEngine, Request

import jax


def _cfg():
    return reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                   d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                   head_dim=32)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("sync_every", 4)
    return BatchedEngine(params, cfg, greedy=True, seed=0, **kw)


def _prompt(rng, n, vocab=128):
    return [int(t) for t in rng.randint(1, vocab, n)]


def _solo_outputs(cfg, params, reqs, **kw):
    """Reference greedy outputs, one uncontended engine run per request
    (greedy + no codec: outputs depend only on the prompt)."""
    outs = {}
    for r in reqs:
        eng = _engine(cfg, params, **kw)
        eng.submit(Request(uid=r.uid, prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens))
        done = list(eng.run())
        assert len(done) == 1
        outs[r.uid] = done[0].out
    return outs


def _oversubscribed(cfg, params, *, preemption):
    """2 slots, 6-page pool (page_size=8): two low-priority shorts hold
    2 pages each, the premium request needs 3 — admissible only if the
    pool gives up pages the shorts hold."""
    eng = _engine(cfg, params, kv_layout="paged", page_size=8, num_pages=6,
                  preemption=preemption)
    rng = np.random.RandomState(3)
    shorts = [Request(uid=i, prompt=_prompt(rng, 4), max_new_tokens=8)
              for i in range(2)]
    premium = Request(uid=9, prompt=_prompt(rng, 20), max_new_tokens=4,
                      priority=1)
    return eng, shorts, premium


def test_evicted_request_resumes_identical_greedy_output(setup):
    cfg, params = setup
    eng, shorts, premium = _oversubscribed(cfg, params, preemption=True)
    ref = _solo_outputs(cfg, params, shorts + [premium],
                        kv_layout="paged", page_size=8, num_pages=6)
    for r in shorts:
        eng.submit(r)
    # run the shorts into mid-decode before the premium request arrives
    eng.tick()
    assert eng.active == 2 and eng.stats["evictions"] == 0
    eng.submit(premium)
    done = {r.uid: r for r in eng.run()}
    assert set(done) == {0, 1, 9}
    assert eng.stats["evictions"] >= 1
    evicted = [r for r in done.values() if r.evictions]
    assert evicted and all(r.priority == 0 for r in evicted)
    assert done[9].evictions == 0          # the preemptor is never a victim
    for uid, r in done.items():
        assert r.out == ref[uid], (uid, r.evictions)
        assert len(r.out) == r.max_new_tokens


def test_premium_overtakes_fifo_only_with_preemption(setup):
    cfg, params = setup
    order = {}
    for preemption in (False, True):
        eng, shorts, premium = _oversubscribed(cfg, params,
                                               preemption=preemption)
        for r in shorts:
            eng.submit(r)
        eng.tick()
        eng.submit(premium)
        done = list(eng.run())
        assert len(done) == 3
        order[preemption] = [r.uid for r in done]
        if not preemption:
            assert eng.stats["evictions"] == 0
            # FIFO: the premium request finishes last, after the shorts
            # drain enough pages
            assert order[False][-1] == 9
    # with preemption the premium request finishes FIRST: it displaced the
    # running shorts instead of waiting out their reservations
    assert order[True][0] == 9


def test_pool_accounting_invariant_across_evictions(setup):
    cfg, params = setup
    eng, shorts, premium = _oversubscribed(cfg, params, preemption=True)
    for r in shorts:
        eng.submit(r)
    eng.tick()
    eng.submit(premium)
    ticks = 0
    while eng.tick():
        acct = eng.pool_accounting()
        assert acct["free"] + acct["in_use"] == acct["total"], acct
        per_slot = [len(s.pages) for s in eng.slots]
        assert sum(per_slot) == acct["in_use"]
        ticks += 1
        assert ticks < 500, "engine failed to drain"
    assert eng.stats["evictions"] >= 1
    acct = eng.pool_accounting()
    assert acct["free"] == acct["total"], acct


def test_equal_priority_never_preempted(setup):
    cfg, params = setup
    eng = _engine(cfg, params, kv_layout="paged", page_size=8, num_pages=6,
                  preemption=True)
    rng = np.random.RandomState(5)
    for i in range(2):
        eng.submit(Request(uid=i, prompt=_prompt(rng, 4), max_new_tokens=8))
    eng.tick()
    # same default priority as the running shorts: must NOT evict them
    eng.submit(Request(uid=9, prompt=_prompt(rng, 20), max_new_tokens=4))
    done = list(eng.run())
    assert len(done) == 3
    assert eng.stats["evictions"] == 0
    assert [r.uid for r in done][-1] == 9


def test_slots_only_preemption_contiguous(setup):
    cfg, params = setup
    eng = _engine(cfg, params, num_slots=1, preemption=True)
    rng = np.random.RandomState(7)
    low = Request(uid=0, prompt=_prompt(rng, 4), max_new_tokens=12)
    high = Request(uid=1, prompt=_prompt(rng, 4), max_new_tokens=4,
                   priority=2)
    ref = _solo_outputs(cfg, params, [low, high], num_slots=1)
    eng.submit(low)
    eng.tick()                     # low occupies the only slot, mid-decode
    eng.submit(high)
    done = {r.uid: r for r in eng.run()}
    assert eng.stats["evictions"] == 1
    assert done[0].evictions == 1
    assert done[0].out == ref[0]   # resumed run == uninterrupted run
    assert done[1].out == ref[1]


def test_eviction_is_feasibility_checked(setup):
    cfg, params = setup
    # 5-page pool: an equal-priority request holds 3 pages (NOT a victim)
    # and the only lower-priority victim holds 2 -- evicting it cannot
    # cover the head's 3-page need, so nothing may be evicted pointlessly
    eng = _engine(cfg, params, kv_layout="paged", page_size=8, num_pages=5,
                  preemption=True)
    rng = np.random.RandomState(11)
    peer = Request(uid=0, prompt=_prompt(rng, 16), max_new_tokens=8,
                   priority=1)                     # 3 pages, same rank as head
    victim = Request(uid=1, prompt=_prompt(rng, 4), max_new_tokens=8)
    eng.submit(peer)
    eng.submit(victim)
    eng.tick()
    assert eng.active == 2         # 5 pages in use, 0 free, both mid-decode
    eng.submit(Request(uid=9, prompt=_prompt(rng, 16), max_new_tokens=8,
                       priority=1))
    done = list(eng.run())
    assert len(done) == 3
    assert eng.stats["evictions"] == 0
    assert all(r.evictions == 0 for r in done)


def test_eos_early_exit_frees_pages_before_boundary(setup):
    cfg, params = setup
    # page_size=4, pool=7: A (prompt 6 + 2 new -> 2 pages) and B (prompt 4
    # + 16 new -> 5 pages) fill the pool; C (5 pages) starves behind them
    eng = _engine(cfg, params, kv_layout="paged", page_size=4, num_pages=7,
                  sync_every=8)
    rng = np.random.RandomState(13)
    a = Request(uid=0, prompt=_prompt(rng, 6), max_new_tokens=2)
    b = Request(uid=1, prompt=_prompt(rng, 4), max_new_tokens=16)
    c = Request(uid=2, prompt=_prompt(rng, 10), max_new_tokens=10)
    eng.submit(a)
    eng.submit(b)
    eng._boundary()
    while eng._pending_prefill():
        eng._prefill_one_chunk()
    assert eng.pool_accounting() == {"free": 0, "in_use": 7, "total": 7}
    eng.submit(c)                  # starved head: needs 5 pages, 0 free
    executed = eng._decode_window(8)
    # A finished mid-window (1 decode step after its prefill-committed
    # token) and the window exited early instead of running all 8 steps
    assert executed < 8
    assert eng.stats["eos_early_exits"] == 1
    # satellite fix: A retired AT THE WINDOW'S HOST SYNC -- outputs at
    # their actual emitted length, whole reservation back on the free
    # list, no _boundary() in between
    assert [r.uid for r in eng.finished] == [0]
    assert len(eng.finished[0].out) == 2
    assert eng.allocator.free_pages == 2
    assert eng.pool_accounting() == {"free": 2, "in_use": 5, "total": 7}
    # and the drain completes normally from there
    done = {r.uid: r for r in eng.run()}
    assert set(done) == {0, 1, 2}
    assert len(done[1].out) == 16 and len(done[2].out) == 10
    acct = eng.pool_accounting()
    assert acct["free"] == acct["total"]


def test_prefill_mode_decode_is_refused(setup):
    """The engine has one prefill path: asking for the removed
    prefill-as-decode mode is an error, not a silent fallback."""
    cfg, params = setup
    removed = "decode"
    with pytest.raises(ValueError, match="prefill_mode='decode' was removed"):
        BatchedEngine(params, cfg, num_slots=2, max_len=32,
                      prefill_mode=removed)
    eng = BatchedEngine(params, cfg, num_slots=2, max_len=32)
    assert not hasattr(eng, "prefill_mode") and not hasattr(eng, "step")
