"""Speculative decoding over the split link (repro.serving.spec).

The contract everything here pins: GREEDY verification makes the
speculative engine's emitted token streams BIT-IDENTICAL to vanilla
decode for every (codec, k, draft head, KV layout) — the draft channel
can only change the acceptance rate (and with it the wire/latency
profile), never an output token.  Around that core:

- rollback is pure position truncation: after rejected rounds the
  committed cache matches a never-speculated engine's position-for-
  position — exactly on integer leaves, and within float-accumulation
  noise (~1e-6; asserted < 1e-4) on KV values, orders of magnitude below
  the O(1) delta a leaked rejected-draft token would leave (hypothesis
  property, contiguous/no-codec layout);
- batch-wise codecs force GROUP-LOCKSTEP acceptance (unit-tested on
  ``accept_lengths`` directly, plus engine equivalence under lockstep
  occupancy — C3-SL outputs are schedule-dependent repo-wide, so the
  codec comparison pins identical dispatch schedules);
- eviction between speculative windows resumes bit-identically and the
  per-request accepted/rejected/rollback counters survive preemption;
- one pre-built program per (R bucket, draft bucket, k): a schedule
  bouncing across all of them never recompiles post-warmup;
- the front-door loopback serves the same tokens as a direct vanilla
  engine, streams TOKENS bursts that prefix the RESULT, and pins the
  draft spec at the handshake;
- wire accounting: verify rounds ship ZERO forward bytes; the draft
  channel's bytes reconcile exactly against the served round schedule.
"""
import asyncio
import dataclasses

import numpy as np
import pytest

import jax

from repro.configs.base import get_config, reduced
from repro.models import lm as lm_lib
from repro.serving.engine import BatchedEngine, Request
from repro.serving.spec import (AdaptiveK, SpecConfig, accept_lengths,
                                token_wire_bytes)

import jax.numpy as jnp


def _cfg(**kw):
    return reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                   d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                   head_dim=32, **kw)


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


def _run(eng, prompts, max_new=8):
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=list(p),
                           max_new_tokens=max_new[i]
                           if isinstance(max_new, (list, tuple)) else max_new))
    done = {r.uid: r for r in eng.run()}
    eng.finished.clear()
    return done


# ---------------------------------------------------------------------------
# bit-identity: speculative == vanilla greedy decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,head", [(2, "copy"), (8, "copy"), (4, "tied")])
def test_bit_identity_no_codec_ragged(setup, k, head):
    """Ragged prompts + staggered finishes (no codec, so occupancy cannot
    leak between rows): every k and both draft heads reproduce vanilla."""
    cfg, params = setup
    rng = np.random.RandomState(1)
    prompts = [_prompt(rng, n) for n in (3, 9, 5)]
    ref = {u: r.out for u, r in _run(_engine(cfg, params), prompts,
                                     max_new=(7, 4, 8)).items()}
    eng = _engine(cfg, params,
                  spec_decode=SpecConfig(k=k, draft_head=head))
    done = _run(eng, prompts, max_new=(7, 4, 8))
    assert {u: r.out for u, r in done.items()} == ref
    assert eng.stats["spec_rounds"] > 0
    # per-request speculative counters folded at retire
    folded = sum(r.accepted + r.rejected for r in done.values())
    assert folded == eng.stats["spec_accepted"] + eng.stats["spec_rejected"]


@pytest.mark.parametrize("k", [2, 4])
def test_bit_identity_codec_lockstep(setup, k):
    """Batch-wise codec: identical-shape requests submitted together run
    in lockstep (same dispatch schedule vanilla and speculative), so the
    group-min acceptance rule must keep superposition contents — and with
    them the outputs — bit-identical."""
    cfg, params = setup
    rng = np.random.RandomState(2)
    prompts = [_prompt(rng, 6), _prompt(rng, 6)]
    ref = {u: r.out
           for u, r in _run(_engine(cfg, params, codec="c3sl:R=2|int8"),
                            prompts, max_new=6).items()}
    eng = _engine(cfg, params, codec="c3sl:R=2|int8",
                  spec_decode=SpecConfig(k=k, draft="c3sl:R=2|int8",
                                         draft_head="tied"))
    done = _run(eng, prompts, max_new=6)
    assert {u: r.out for u, r in done.items()} == ref
    assert eng.stats["spec_rounds"] > 0
    assert eng.stats["wire_bytes_draft"] > 0


@pytest.mark.parametrize("layout", ["ring_swa", "int8_kv", "paged_gather",
                                    "paged_kernel"])
def test_bit_identity_kv_layouts(layout):
    """The commit path's valid-masked chunk re-ingest must agree with
    vanilla per-token decode on every KV layout: ring-SWA (aliased ring
    writes), quantized int8 KV, and the paged pool under both read
    paths."""
    cfg = _cfg()
    kw = {}
    if layout == "ring_swa":
        cfg = dataclasses.replace(cfg, sliding_window=8)
    elif layout == "int8_kv":
        cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    else:
        kw = {"kv_layout": "paged", "page_size": 8, "num_pages": 8,
              "kv_read": "kernel" if layout == "paged_kernel" else "gather"}
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    prompts = [_prompt(rng, 4), _prompt(rng, 7)]
    ref = {u: r.out for u, r in _run(_engine(cfg, params, **kw), prompts,
                                     max_new=6).items()}
    eng = _engine(cfg, params, spec_decode=SpecConfig(k=4, draft_head="copy"),
                  **kw)
    done = _run(eng, prompts, max_new=6)
    assert {u: r.out for u, r in done.items()} == ref
    assert eng.stats["spec_rounds"] > 0


# ---------------------------------------------------------------------------
# rollback property: the cache never sees a speculative write
# ---------------------------------------------------------------------------

@pytest.mark.property
def test_rollback_cache_property(setup):
    """Hypothesis property: after ANY workload (ragged prompts, budgets
    drawn adversarially) with rejections in it, the speculative engine's
    emitted streams equal a never-speculated engine's BIT-FOR-BIT and
    its cache matches position-for-position.  Verify-phase cache writes
    are discarded in-graph and commit re-ingests only accepted tokens,
    so not one rejected position may leak into KV state — a leak writes
    the WRONG token's KV (an O(1) delta for this model); the only
    tolerated difference is float accumulation order between the
    chunked commit path and vanilla's per-token decode writes (~1e-6,
    asserted < 1e-4)."""
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need the optional hypothesis package")
    from hypothesis import given, settings, strategies as st
    cfg, params = setup
    vanilla = _engine(cfg, params)
    spec = _engine(cfg, params, spec_decode=SpecConfig(k=4,
                                                       draft_head="tied"))
    seen_rollback = [0]

    @settings(deadline=None)
    @given(st.lists(st.tuples(st.integers(2, 10), st.integers(1, 8)),
                    min_size=1, max_size=2),
           st.integers(0, 2 ** 31 - 1))
    def prop(shapes, seed):
        rng = np.random.RandomState(seed)
        prompts = [_prompt(rng, n) for n, _ in shapes]
        max_new = [m for _, m in shapes]
        ref = _run(vanilla, prompts, max_new=max_new)
        got = _run(spec, prompts, max_new=max_new)
        assert {u: r.out for u, r in got.items()} == \
               {u: r.out for u, r in ref.items()}
        for a, b in zip(jax.tree.leaves(vanilla.cache),
                        jax.tree.leaves(spec.cache)):
            a, b = np.asarray(a), np.asarray(b)
            if np.issubdtype(a.dtype, np.integer):
                assert np.array_equal(a, b)      # positions/pages: exact
            else:
                delta = np.max(np.abs(a - b)) if a.size else 0.0
                assert delta < 1e-4, (
                    f"cache leaf diverged by {delta} — a rejected draft "
                    "leaked into committed KV state")
        seen_rollback[0] += sum(r.rollbacks for r in got.values())

    prop()
    assert seen_rollback[0] > 0, (
        "no drawn workload ever rejected a draft — the rollback path was "
        "never exercised and the property is vacuous")


def test_eviction_during_speculation_resumes_identical(setup):
    """A slot evicted between speculative windows re-prefills prompt +
    emitted tokens and resumes bit-identically; its folded
    accepted/rejected/rollback counters survive the preemption."""
    cfg, params = setup
    spec_kw = dict(kv_layout="paged", page_size=8, num_pages=6,
                   preemption=True,
                   spec_decode=SpecConfig(k=2, draft_head="tied"))
    rng = np.random.RandomState(4)
    shorts = [Request(uid=i, prompt=_prompt(rng, 4), max_new_tokens=8)
              for i in range(2)]
    premium = Request(uid=9, prompt=_prompt(rng, 20), max_new_tokens=4,
                      priority=1)
    # reference: vanilla solo runs (greedy + no codec: prompt-determined)
    ref = {}
    for r in shorts + [premium]:
        v = _engine(cfg, params, kv_layout="paged", page_size=8, num_pages=6)
        v.submit(Request(uid=r.uid, prompt=list(r.prompt),
                         max_new_tokens=r.max_new_tokens))
        ref[r.uid] = list(v.run())[0].out
        v.finished.clear()
    eng = _engine(cfg, params, **spec_kw)
    for r in shorts:
        eng.submit(r)
    eng.tick()                       # both shorts into mid-decode
    assert eng.active == 2 and eng.stats["evictions"] == 0
    eng.submit(premium)
    done = {r.uid: r for r in eng.run()}
    assert eng.stats["evictions"] >= 1
    assert {u: r.out for u, r in done.items()} == ref
    for r in done.values():
        assert r.accepted >= 0 and r.rejected >= 0 and r.rollbacks >= 0
    folded = sum(r.accepted for r in done.values())
    assert folded == eng.stats["spec_accepted"], (
        "per-request accepted counters lost across eviction")


# ---------------------------------------------------------------------------
# program table: zero post-warmup recompiles across (R, draft-R, k)
# ---------------------------------------------------------------------------

def test_zero_recompiles_across_r_and_k_switches(setup):
    """One pre-built program per (engine R bucket, draft bucket, k>1);
    bouncing the R pin and the k pin across every combination reuses the
    warm programs — each jit entry holds exactly one compiled trace."""
    cfg, params = setup
    eng = _engine(cfg, params, num_slots=4,
                  codec="adaptive:c3sl:R=4,min_R=2|int8",
                  spec_decode=SpecConfig(k=2, ladder=(1, 2, 4),
                                         draft="c3sl:R=2|int8",
                                         draft_head="tied"))
    assert set(eng._spec_programs) == {(R, None, k)
                                      for R in (2, 4) for k in (2, 4)}
    progs = dict(eng._spec_programs)
    rng = np.random.RandomState(5)
    for R, k in ((2, 2), (4, 4), (2, 4), (4, 2), (2, 2)):
        eng.codec.pin(R)
        eng._k_ctl.pin(k)
        for u in range(2):
            eng.submit(Request(uid=100 * R + 10 * k + u,
                               prompt=_prompt(rng, 4), max_new_tokens=4))
        eng.run()
        eng.finished.clear()
    assert all(eng._spec_programs[key] is progs[key] for key in progs), \
        "spec program table was rebuilt mid-flight"
    for key, prog in eng._spec_programs.items():
        if hasattr(prog, "_cache_size"):
            assert prog._cache_size() <= 1, (
                f"spec program {key} retraced: {prog._cache_size()} entries")
    assert set(eng.k_served) == {2, 4}


# ---------------------------------------------------------------------------
# front-door loopback: bit-identity + TOKENS streaming + draft handshake
# ---------------------------------------------------------------------------

def test_frontdoor_loopback_spec_bit_identity(setup):
    from repro.frontdoor.client import FrontDoorClient, FrontDoorError
    from repro.frontdoor.server import FrontDoorServer
    cfg, params = setup
    rng = np.random.RandomState(6)
    prompts = [_prompt(rng, 3 + 2 * i) for i in range(3)]
    ref = {u: r.out for u, r in _run(_engine(cfg, params), prompts,
                                     max_new=6).items()}

    async def loop():
        eng = _engine(cfg, params,
                      spec_decode=SpecConfig(k=4, draft_head="tied"))
        server = FrontDoorServer(eng)
        host, port = await server.start()
        bursts = []
        client = await FrontDoorClient.open(
            host, port, tenant="spec-t",
            on_tokens=lambda rid, toks: bursts.append((rid, toks)))
        # HELLO_OK advertises the pinned speculative contract
        assert client.server_info["spec_k"] == 4
        assert client.server_info["draft_head"] == "tied"
        assert client.server_info["draft"] == "none"   # raw f32 feedback
        outs = []
        try:
            for p in prompts:            # sequential: lockstep-free anyway
                outs.append(await client.generate(p, max_new=6))
        finally:
            await client.close()
            await server.stop()
        assert server.tick_error is None
        return outs, bursts

    outs, bursts = asyncio.run(loop())
    assert [o["tokens"] for o in outs] == [ref[u] for u in sorted(ref)]
    for o in outs:
        # TOKENS frames previewed a prefix of the final result, and on a
        # healthy loopback connection the whole output streamed
        assert o["streamed"] == o["tokens"]
        assert o["ttlt_s"] is not None and o["ttlt_s"] >= 0
        assert o["accepted"] + o["rejected"] > 0
    assert bursts and all(toks for _, toks in bursts)

    async def mismatched_draft():
        eng = _engine(cfg, params,
                      spec_decode=SpecConfig(k=2, draft="c3sl:R=2|int8"))
        server = FrontDoorServer(eng)
        host, port = await server.start()
        try:
            await FrontDoorClient.open(host, port, tenant="bad",
                                       draft="none", reconnect=False)
        finally:
            await server.stop()

    with pytest.raises(FrontDoorError, match="draft-channel mismatch"):
        asyncio.run(mismatched_draft())


# ---------------------------------------------------------------------------
# wire accounting
# ---------------------------------------------------------------------------

def test_wire_accounting_verify_rounds_ship_zero_fwd(setup):
    """Speculative decode windows ship NOTHING on the forward channel
    (server-side bottom-stack replay): forward bytes shrink to the
    prefill chunks, the draft channel's total reconciles exactly against
    the served round schedule, and wire_per_token stays consistent with
    the raw counters."""
    cfg, params = setup
    rng = np.random.RandomState(8)
    prompts = [_prompt(rng, 6), _prompt(rng, 6)]

    base = _engine(cfg, params, codec="c3sl:R=2|int8")
    base_done = _run(base, prompts, max_new=8)
    spec = _engine(cfg, params, codec="c3sl:R=2|int8",
                   spec_decode=SpecConfig(k=4, draft="c3sl:R=2|int8",
                                          draft_head="tied"))
    spec_done = _run(spec, prompts, max_new=8)
    assert {u: r.out for u, r in spec_done.items()} == \
           {u: r.out for u, r in base_done.items()}

    assert base.stats["wire_bytes_draft"] == 0
    assert spec.stats["wire_bytes_fwd"] < base.stats["wire_bytes_fwd"], \
        "verify rounds still shipped forward payloads"
    assert spec.stats["wire_bytes_draft"] == sum(
        rounds * spec._draft_round_wire_bytes(k)
        for k, rounds in spec.k_served.items())
    wpt = spec.wire_per_token()
    assert wpt["wire_bytes_fwd"] == spec.stats["payload_wire_bytes"]
    assert wpt["generated_tokens"] == sum(len(r.out)
                                          for r in spec_done.values())
    assert wpt["wire_bytes_per_token"] == pytest.approx(
        (wpt["wire_bytes_fwd"] + wpt["wire_bytes_draft"])
        / wpt["generated_tokens"])


def test_token_wire_bytes():
    assert token_wire_bytes(256) == 1
    assert token_wire_bytes(257) == 2
    assert token_wire_bytes(1 << 16) == 2
    assert token_wire_bytes((1 << 16) + 1) == 4


# ---------------------------------------------------------------------------
# accept_lengths: the group-lockstep acceptance rule
# ---------------------------------------------------------------------------

def _accept(fed, targets, live, **kw):
    kw.setdefault("group", 1)
    kw.setdefault("eos_id", None)
    B = len(fed)
    kw.setdefault("rem_new", jnp.full((B,), 99, jnp.int32))
    kw.setdefault("rem_pos", jnp.full((B,), 99, jnp.int32))
    return np.asarray(accept_lengths(jnp.asarray(fed, jnp.int32),
                                     jnp.asarray(targets, jnp.int32),
                                     jnp.asarray(live), **kw))


def test_accept_lengths_prefix_rule():
    fed = [[5, 7, 8, 9]]                  # last verified tok + 3 drafts
    assert _accept(fed, [[7, 8, 9, 1]], [True]).tolist() == [4]   # all match
    assert _accept(fed, [[7, 8, 2, 1]], [True]).tolist() == [3]
    assert _accept(fed, [[7, 1, 9, 1]], [True]).tolist() == [2]
    assert _accept(fed, [[1, 8, 9, 1]], [True]).tolist() == [1]   # floor 1


def test_accept_lengths_eos_and_budget_caps():
    fed = [[5, 7, 8, 9]]
    targets = [[7, 8, 9, 1]]              # would accept 4
    assert _accept(fed, [[7, 0, 9, 1]], [True], eos_id=0).tolist() == [2]
    assert _accept([[5, 0, 8, 9]], [[0, 8, 9, 1]], [True],
                   eos_id=0).tolist() == [1]        # EOS target at pos 0
    assert _accept(fed, targets, [True],
                   rem_new=jnp.asarray([2])).tolist() == [2]
    assert _accept(fed, targets, [True],
                   rem_pos=jnp.asarray([0])).tolist() == [1]   # floor stays 1


def test_accept_lengths_group_lockstep_and_dead_rows():
    fed = [[5, 7, 8, 9], [5, 7, 8, 9]]
    targets = [[7, 8, 9, 1], [7, 2, 9, 1]]          # rows accept 4 and 2
    assert _accept(fed, targets, [True, True]).tolist() == [4, 2]
    assert _accept(fed, targets, [True, True], group=2).tolist() == [2, 2]
    # a DEAD partner must never cap its group
    assert _accept(fed, targets, [True, False], group=2).tolist() == [4, 4]


# ---------------------------------------------------------------------------
# SpecConfig / AdaptiveK / engine validation
# ---------------------------------------------------------------------------

def test_spec_config_validation():
    with pytest.raises(ValueError, match="powers of two"):
        SpecConfig(k=3, ladder=(1, 3))
    with pytest.raises(ValueError, match="not in ladder"):
        SpecConfig(k=8, ladder=(1, 2, 4))
    with pytest.raises(ValueError, match="draft_head"):
        SpecConfig(draft_head="oracle")
    with pytest.raises(ValueError, match="ema"):
        SpecConfig(ema=1.0)
    assert SpecConfig(draft_head="copy").needs_feedback is False
    assert SpecConfig(draft_head="tied").needs_feedback is True


def test_engine_spec_validation(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="greedy"):
        BatchedEngine(params, cfg, num_slots=2, max_len=32, greedy=False,
                      spec_decode=SpecConfig())
    swa = dataclasses.replace(cfg, sliding_window=4)
    swa_params = lm_lib.init_lm_params(jax.random.PRNGKey(0), swa)
    with pytest.raises(ValueError, match="sliding_window"):
        BatchedEngine(swa_params, swa, num_slots=2, max_len=32,
                      spec_decode=SpecConfig(k=8))
    # a link spec's draft: segment auto-enables speculation
    eng = _engine(cfg, params, codec="c3sl:R=2|int8 >> draft:c3sl:R=2|int8")
    assert eng.spec_cfg is not None and eng.draft_codec is not None


def test_adaptive_k_controller():
    cfg = SpecConfig(k=2, ladder=(1, 2, 4, 8), adaptive=True,
                     target_accept=0.5, ema=0.0, hysteresis=0.1)
    ctl = AdaptiveK(cfg)
    assert ctl.current_k == 2
    assert ctl.observe(0.9) == 4                     # above band: ramp up
    assert ctl.observe(0.9) == 8
    assert ctl.observe(0.9) == 8                     # ladder top: hold
    assert ctl.observe(0.5) == 8                     # inside deadband: hold
    assert ctl.observe(0.1) == 4                     # below band: ramp down
    assert ctl.observe(0.1) == 2
    assert ctl.observe(0.1) == 1                     # k=1 == speculation off
    assert ctl.observe(None) == 1                    # no signal: hold
    ctl.pin(8)
    assert ctl.observe(0.0) == 8                     # pinned: schedule fixed
    ctl.unpin()
    assert ctl.observe(0.0) == 4
    with pytest.raises(ValueError, match="not in ladder"):
        ctl.pin(16)
    # non-adaptive configs come up pinned at cfg.k
    fixed = AdaptiveK(SpecConfig(k=4))
    assert fixed.observe(1.0) == 4 and fixed.observe(0.0) == 4
