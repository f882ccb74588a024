"""Continuous-batching engine tests: per-slot positions, slot recycling,
and equivalence with lockstep decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models import lm as lm_lib
from repro.serving.engine import BatchedEngine, Request


def _setup(num_slots=4, max_len=32):
    cfg = reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                  d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = BatchedEngine(params, cfg, num_slots=num_slots, max_len=max_len,
                        greedy=True)
    return cfg, params, eng


def test_engine_completes_all_requests_with_recycling():
    cfg, params, eng = _setup(num_slots=2)
    reqs = [Request(uid=i, prompt=[1 + i, 2 + i, 3 + i], max_new_tokens=4)
            for i in range(5)]  # 5 requests through 2 slots -> recycling
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.out) == 4 for r in done)
    assert all(all(0 <= t < cfg.vocab_size for t in r.out) for r in done)


def test_engine_matches_lockstep_decode():
    """A single request through the engine must equal greedy lockstep
    decoding with the plain decode_step."""
    cfg, params, eng = _setup(num_slots=3)
    prompt = [5, 17, 23, 2]
    eng.submit(Request(uid=0, prompt=list(prompt), max_new_tokens=5))
    done = eng.run()
    got = done[0].out

    # reference: scalar-pos decode with batch 1
    cache = lm_lib.init_decode_cache(params, cfg, 1, 32)
    step = jax.jit(lambda p, c, t, pos: lm_lib.decode_step(p, c, t, pos, cfg))
    toks = list(prompt)
    out = []
    pos = 0
    cur = prompt
    logits = None
    for t in prompt:
        logits, cache = step(params, cache,
                             jnp.asarray([[t]], jnp.int32), jnp.int32(pos))
        pos += 1
    for _ in range(5):
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        logits, cache = step(params, cache,
                             jnp.asarray([[nxt]], jnp.int32), jnp.int32(pos))
        pos += 1
    assert got == out, (got, out)


def test_vector_pos_equals_scalar_pos():
    """decode_step with pos (B,) of equal values == scalar pos."""
    cfg, params, _ = _setup()
    B = 3
    cache_a = lm_lib.init_decode_cache(params, cfg, B, 16)
    cache_b = lm_lib.init_decode_cache(params, cfg, B, 16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab_size)
    la, _ = lm_lib.decode_step(params, cache_a, toks, jnp.int32(0), cfg)
    lb, _ = lm_lib.decode_step(params, cache_b, toks,
                               jnp.zeros((B,), jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5,
                               atol=1e-5)


def test_engine_with_c3sl_codec_and_int8_cache():
    """Full serving stack: continuous batching + C3-SL boundary codec +
    int8 KV cache, all at once."""
    import dataclasses
    from repro.core.codec import C3SLCodec
    cfg = reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                  d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    codec = C3SLCodec(R=2, D=cfg.d_model)
    eng = BatchedEngine(params, cfg, num_slots=2, max_len=32,
                        codec=codec, codec_params=codec.init(jax.random.PRNGKey(7)))
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[1 + i, 2 + i], max_new_tokens=3))
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.out) == 3 for r in done)


def test_submit_rejects_overlong_and_empty_prompts():
    """Prompts that leave no decode position are rejected AT SUBMIT with a
    clear error instead of coming back short.  Regression: a prompt of
    exactly max_len used to be admitted, prefilled, and cut off after one
    token regardless of max_new_tokens (finish_check fires at
    pos >= max_len) — it must be rejected, not silently truncated."""
    cfg, params, eng = _setup(num_slots=2, max_len=8)
    with pytest.raises(ValueError, match="max_len=8"):
        eng.submit(Request(uid=0, prompt=list(range(1, 10)), max_new_tokens=2))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(uid=1, prompt=[], max_new_tokens=2))
    # the old silent-truncation case: len(prompt) == max_len
    with pytest.raises(ValueError, match="no decode positions"):
        eng.submit(Request(uid=2, prompt=[1, 2, 3, 4, 5, 6, 7, 2],
                           max_new_tokens=4))
    # boundary case max_len - 1 is admitted; generation is still capped by
    # the cache (1 prefill-predicted token + 1 decoded position), never 0
    eng.submit(Request(uid=3, prompt=[1, 2, 3, 4, 5, 6, 7], max_new_tokens=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].out) == 2


def test_slot_reset_is_layout_aware():
    """Regression for the old shape heuristic: with max_len == num_slots an
    UNSTACKED first-dense cache leaf (B, T, ...) has shape[1] == num_slots,
    and `leaf.at[:, idx].set(0)` would zero cache POSITION idx across every
    slot (corrupting all in-flight rows) instead of slot idx's row."""
    cfg = reduced(get_config("deepseek-v2-lite-16b"))   # has first_dense_layers
    assert cfg.first_dense_layers
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    n = 8
    eng = BatchedEngine(params, cfg, num_slots=n, max_len=n)
    eng.cache = jax.tree.map(jnp.ones_like, eng.cache)
    eng.cache = eng._reset(eng.cache, jnp.asarray(np.arange(n) == 0))
    first = eng.cache["first"]["l0_0_mla"]["c_kv"]      # (B, T, L), T == B
    assert np.asarray(first[0]).max() == 0.0            # slot 0 cleared
    assert np.asarray(first[1:]).min() == 1.0           # other slots intact
    stacked = eng.cache["stack"]["l0_0_mla"]["c_kv"]    # (N, B, T, L)
    assert np.asarray(stacked[:, 0]).max() == 0.0
    assert np.asarray(stacked[:, 1:]).min() == 1.0


def test_drained_batch_exits_decode_window_early():
    """Regression: the run loop used to dispatch the full `sync_every`
    donated steps before checking EOS flags, so a batch that drained on
    step 1 paid sync_every - 1 wasted dispatches per boundary.  Decode now
    runs as ONE jitted window whose device-side while_loop stops the moment
    no slot is live: dispatch and step counts must reflect that."""
    cfg, params, eng = _setup(num_slots=2, max_len=32)
    assert eng.sync_every == 8
    eng.submit(Request(uid=0, prompt=[3, 5, 7], max_new_tokens=2))
    done = eng.run()
    assert len(done) == 1 and len(done[0].out) == 2
    # 1 generated in prefill + 1 decode step; the old loop would have run 8
    assert eng.stats["decode_steps"] == 1
    # one prefill chunk + one decode window (not 8 step dispatches)
    assert eng.stats["prefill_chunks"] == 1
    assert eng.stats["dispatches"] == 2


def test_interleave_scheduler_outputs_invariant():
    """interleave > 0 alternates prefill chunks with bounded decode windows
    (TTFT/throughput knob); without a codec rows are independent, so every
    request's GREEDY tokens must be IDENTICAL at any interleave setting
    (sampling would consume a different key schedule per setting)."""
    cfg = reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                  d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(31)
    lens = [2, 17, 5, 21, 3]                 # long prompts admitted mid-decode
    reqs = [list(map(int, rng.randint(2, cfg.vocab_size, n))) for n in lens]
    outs = []
    for il in (0, 1, 3):
        eng = BatchedEngine(params, cfg, num_slots=2, max_len=48, eos_id=1,
                            chunk_size=4, sync_every=4, interleave=il)
        for u, p in enumerate(reqs):
            eng.submit(Request(uid=u, prompt=list(p), max_new_tokens=6))
        outs.append({r.uid: r.out for r in eng.run()})
        assert len(outs[-1]) == len(reqs)
    assert outs[0] == outs[1] == outs[2]


def test_requests_report_time_to_first_token():
    cfg, params, eng = _setup(num_slots=2)
    for u in range(3):
        eng.submit(Request(uid=u, prompt=[2 + u, 3, 4], max_new_tokens=3))
    done = eng.run()
    for r in done:
        assert r.t_first is not None and r.t_first >= r.t_submit > 0


def test_staggered_positions_are_independent():
    """Slots at different positions don't contaminate each other: decoding
    row 0 at pos 3 while row 1 sits at pos 0 gives the same logits for row 0
    as a batch where all rows are at pos 3 with the same history."""
    cfg, params, _ = _setup()
    B, T = 2, 16
    history = [7, 11, 13]
    step = jax.jit(lambda p, c, t, pos: lm_lib.decode_step(p, c, t, pos, cfg))

    # batch where both rows see the history
    cache = lm_lib.init_decode_cache(params, cfg, B, T)
    logits = None
    for i, t in enumerate(history):
        logits, cache = step(params, cache,
                             jnp.asarray([[t], [t]], jnp.int32),
                             jnp.full((B,), i, jnp.int32))
    ref = np.asarray(logits[0, -1])

    # batch where row 1 lags (its token differs and its pos stays 0)
    cache2 = lm_lib.init_decode_cache(params, cfg, B, T)
    logits2 = None
    for i, t in enumerate(history):
        logits2, cache2 = step(params, cache2,
                               jnp.asarray([[t], [99]], jnp.int32),
                               jnp.asarray([i, 0], jnp.int32))
    got = np.asarray(logits2[0, -1])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("adaptive_spec,static_spec", [
    ("adaptive:c3sl:R=4,min_R=2", "c3sl:R=2"),
    ("adaptive:c3sl:R=4,min_R=2|int8", "c3sl:R=2|int8"),
])
def test_adaptive_pinned_engine_matches_static_greedy_decode(adaptive_spec,
                                                              static_spec):
    """AdaptiveC3SL pinned to a constant schedule through a BatchedEngine
    greedy decode is bit-identical to the static codec — including the
    |int8 chain: the engine's per-bucket programs close over the same
    bucket codec + params the static engine compiles."""
    cfg = reduced(get_config("deepseek-7b"), num_layers=2, d_model=128,
                  d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = [([5, 17, 23, 2], 5), ([7, 7, 9], 4), ([3, 11], 3)]
    outs = {}
    for name, spec in (("static", static_spec), ("adaptive", adaptive_spec)):
        eng = BatchedEngine(params, cfg, num_slots=2, max_len=32,
                            codec=spec, greedy=True)
        if name == "adaptive":
            eng.codec.pin(2)
        for u, (p, mn) in enumerate(reqs):
            eng.submit(Request(uid=u, prompt=list(p), max_new_tokens=mn))
        outs[name] = {r.uid: r.out for r in eng.run(max_steps=128)}
        assert len(outs[name]) == len(reqs)
    assert outs["adaptive"] == outs["static"]
