"""A prefill chunk computes only the codec slot groups that hold a
prefilling slot.

The codec superposes R consecutive slots at each position of a chunk, so
group g (slots g*R ... g*R+R-1; one slot without a codec) can run apart
from the others.  The engine's prefill program takes the ascending group
indices to compute (None for all), gathers those rows of the tokens, mask,
positions, page tables and per-slot cache leaves, and scatters the
per-slot leaves and the slot state back.  Here: one group gives the same
logits, state and cache as the whole chunk, bit for bit; slots outside the
groups come back untouched, for every cache layout the engine builds; the
program traces once per group count; ``prefill_rows`` counts the rows
dispatched; and an engine whose prompts arrive staggered emits the same
tokens as one whose prompts arrive together.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models import lm as lm_lib
from repro.serving import engine as engine_lib
from repro.serving.engine import BatchedEngine, Request

B, T, PS, C = 8, 32, 4, 8

# (arch, config overrides, engine keyword arguments)
CASES = {
    "paged_gqa": ("deepseek-7b", {}, dict(kv_layout="paged",
                                          codec="c3sl:R=2|int8")),
    "contiguous": ("deepseek-7b", {}, dict(codec="c3sl:R=2|int8")),
    "swa_ring": ("deepseek-7b", {"sliding_window": 8},
                 dict(kv_layout="paged", codec="c3sl:R=2|int8")),
    "mla_first_dense": ("deepseek-v2-lite-16b", {},
                        dict(kv_layout="paged", codec="c3sl:R=2|int8")),
    "rwkv": ("rwkv6-1.6b", {}, dict(codec="c3sl:R=2|int8")),
    "no_codec": ("deepseek-7b", {}, dict(kv_layout="paged")),
}


@functools.lru_cache(maxsize=None)
def _model(arch, over):
    cfg = reduced(get_config(arch), d_model=64, num_heads=4, head_dim=16,
                  d_ff=128, vocab_size=64, **dict(over))
    return cfg, lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)


def _engine(case, **extra):
    arch, over, kw = CASES[case]
    cfg, params = _model(arch, tuple(over.items()))
    kw = {**dict(num_slots=B, max_len=T, chunk_size=C, page_size=PS,
                 sync_every=4, greedy=True, seed=0), **kw, **extra}
    return BatchedEngine(params, cfg, **kw)


def _noisy_inputs(eng, rng, live):
    """Host copies of a cache filled with noise (so a read or write of the
    wrong slot, page or layer cannot hide behind zeros), permuted page
    tables, a slot state with distinct positions, and one chunk whose
    valid tokens sit in the slots ``live`` (ragged lengths)."""
    cache = {k: (jax.tree.map(lambda a: (rng.randn(*a.shape) * 3).astype(
                 a.dtype), v) if k in ("stack", "first")
                 else jax.tree.map(np.asarray, v))
             for k, v in eng.cache.items()}
    for key in ("pages", "pages_swa"):
        if key in cache:
            n = cache[key].size
            cache[key] = rng.permutation(n).astype(np.int32).reshape(
                cache[key].shape)
    state = jax.tree.map(np.array, eng.state)
    state["pos"] = rng.randint(0, T - C, B).astype(np.int32)
    state["last_tok"] = rng.randint(1, 64, B).astype(np.int32)
    state["out_len"] = rng.randint(0, 4, B).astype(np.int32)
    state["max_new"][:] = 8
    tokens = np.zeros((B, C), np.int32)
    valid = np.zeros((B, C), bool)
    completes = np.zeros((B,), bool)
    for n, i in zip((C, 5, 3, 1), live):
        tokens[i, :n] = rng.randint(1, 64, n)
        valid[i, :n] = True
        completes[i] = n < C
    return cache, state, tokens, valid, completes


def _dispatch(eng, inputs, groups):
    cache, state, tokens, valid, completes = inputs
    out = eng._programs[None]["prefill"](
        eng.params, jax.tree.map(jnp.asarray, cache),
        jax.tree.map(jnp.asarray, state), jnp.asarray(tokens),
        jnp.asarray(valid), jnp.asarray(completes), jax.random.PRNGKey(3),
        groups)
    return jax.tree.map(np.asarray, out)


# The CPU's dot kernels for a product of k*R*C rows and one of B*C rows
# accumulate in different orders (XLA picks the kernel by shape), so
# floats computed from a different row count differ in their last bits.
# Where that applies, float leaves are compared to this tolerance; integer
# leaves (positions, tokens, page tables, flags) always exactly.
TOL = dict(rtol=1e-5, atol=1e-5)


def _assert_trees_equal(got, want, tol=None):
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    ref = jax.tree.leaves(want)
    assert len(flat) == len(ref)
    for (path, g), r in zip(flat, ref):
        where = jax.tree_util.keystr(path)
        if tol is not None and np.issubdtype(g.dtype, np.floating):
            np.testing.assert_allclose(g, r, err_msg=where, **tol)
        else:
            np.testing.assert_array_equal(g, r, err_msg=where)


@pytest.mark.parametrize("case", list(CASES))
def test_one_group_equals_every_group(case):
    """(a) One chunk whose prefilling slots all lie in one group: the
    program run on that group alone and on every group gives the same
    logits in the live rows, the same slot state (exactly) and the same
    cache, the pages it writes included (to ``TOL``)."""
    eng = _engine(case)
    width = engine_lib._group_rows(eng._current_codec(), B)
    g = 1
    live = range(g * width, (g + 1) * width)
    inputs = _noisy_inputs(eng, np.random.RandomState(7), live)
    one = _dispatch(eng, inputs, jnp.asarray([g], jnp.int32))
    every = _dispatch(eng, inputs, None)
    _assert_trees_equal(one[1], every[1])
    _assert_trees_equal(one[0], every[0], TOL)

    cache, state, tokens, valid, _ = inputs
    logits = jax.jit(functools.partial(
        engine_lib.prefill_groups, cfg=eng.cfg, width=width,
        codec=eng._current_codec(), codec_params=eng.codec_params,
        paged=eng.paged))
    args = (eng.params, jax.tree.map(jnp.asarray, cache),
            jnp.asarray(tokens), jnp.asarray(state["pos"]))
    lg_one, _ = logits(*args, groups=jnp.asarray([g], jnp.int32),
                       valid=jnp.asarray(valid))
    lg_all, _ = logits(*args, groups=None, valid=jnp.asarray(valid))
    rows = list(live)
    np.testing.assert_allclose(np.asarray(lg_one)[rows],
                               np.asarray(lg_all)[rows], **TOL)
    assert not np.asarray(lg_one)[[i for i in range(B) if i not in rows]].any()


@pytest.mark.parametrize("case", list(CASES))
def test_slots_outside_groups_come_back_untouched(case):
    """(b) Two groups dispatched, one of them padding: every per-slot cache
    leaf and every state entry of the slots outside them, and every pool
    page that no slot of theirs owns, comes back bit for bit."""
    eng = _engine(case)
    width = engine_lib._group_rows(eng._current_codec(), B)
    n = B // width
    live = [width * (n - 1) + width - 1]            # last slot of the last group
    groups = [0, n - 1]
    inputs = _noisy_inputs(eng, np.random.RandomState(11), live)
    cache0, state0 = inputs[0], inputs[1]
    cache1, state1 = _dispatch(eng, inputs, jnp.asarray(groups, jnp.int32))
    mine = [g * width + j for g in groups for j in range(width)]
    others = [i for i in range(B) if i not in mine]
    for key in state0:
        np.testing.assert_array_equal(state1[key][others], state0[key][others],
                                      err_msg=key)
    axes = engine_lib._slot_axes(cache0, eng.paged)
    flat = jax.tree_util.tree_flatten_with_path(axes)[0]
    leaves0, leaves1 = jax.tree.leaves(cache0), jax.tree.leaves(cache1)
    checked_pool = False
    for (path, ax), a, b in zip(flat, leaves0, leaves1):
        where = jax.tree_util.keystr(path)
        if ax >= 0:
            np.testing.assert_array_equal(np.take(b, others, axis=ax),
                                          np.take(a, others, axis=ax),
                                          err_msg=where)
            continue
        if "stack" not in where and "first" not in where:
            np.testing.assert_array_equal(b, a, err_msg=where)
            continue
        # a pool: the pages the dispatched slots own may change, no other
        ring = "attn" in where and eng.cfg.sliding_window
        table = cache0["pages_swa" if ring else "pages"]
        owned = set(table[mine].ravel().tolist())
        pax = 1 if "stack" in where else 0
        keep = [p for p in range(a.shape[pax]) if p not in owned]
        np.testing.assert_array_equal(np.take(b, keep, axis=pax),
                                      np.take(a, keep, axis=pax),
                                      err_msg=where)
        checked_pool = True
    assert checked_pool == (eng.paged is not None)


@pytest.mark.parametrize("codec,counts", [
    ("c3sl:R=2|int8", {None: [1, 2, 4]}),
    ("adaptive:c3sl:R=4,min_R=2|int8", {2: [1, 2, 4], 4: [1, 2]}),
    (None, {None: [1, 2, 4, 8]}),
])
def test_one_trace_per_group_count(codec, counts):
    """(c) A bucket's first chunk traces its prefill program once per group
    count, with all-masked dispatches that leave cache and state as they
    were; chunks of every count afterwards add no trace."""
    eng = _engine("paged_gqa", codec=codec)
    progs = {b: eng._programs[b]["prefill"] for b in counts}
    assert all(p._cache_size() == 0 for p in progs.values())
    rng = np.random.RandomState(3)
    # warming leaves a noisy cache and state as they are
    for bucket in counts:
        eng.cache, eng.state = (jax.tree.map(jnp.asarray, t)
                                for t in _noisy_inputs(eng, rng, [])[:2])
        before = jax.tree.map(np.asarray, (eng.cache, eng.state))
        if eng._adaptive:
            eng.codec.pin(bucket)
        eng._warm_prefill(bucket, engine_lib._group_rows(eng._current_codec(),
                                                         B))
        _assert_trees_equal(jax.tree.map(np.asarray, (eng.cache, eng.state)),
                            before)
        assert progs[bucket]._cache_size() == len(counts[bucket])

    eng = _engine("paged_gqa", codec=codec)
    progs = {b: eng._programs[b]["prefill"] for b in counts}
    seen = {b: set() for b in counts}
    dispatch = eng._dispatch_groups

    def spy(slots, width):
        groups, k = dispatch(slots, width)
        seen[eng._bucket()].add(k)
        return groups, k

    eng._dispatch_groups = spy
    for bucket in counts:
        if eng._adaptive:
            eng.codec.pin(bucket)
        # the prefilling slots thin out chunk by chunk: all, slots 5-7,
        # slots 6-7, slot 7
        for u, n in enumerate([1, 2, 3, 4, 5, 12, 20, 28]):
            eng.submit(Request(uid=100 * (bucket or 1) + u,
                               prompt=[1 + u] * n, max_new_tokens=2))
        eng._boundary()
        eng._prefill_one_chunk()          # the bucket's first chunk warms it
        assert progs[bucket]._cache_size() == len(counts[bucket])
        eng.run(max_steps=200)
        assert progs[bucket]._cache_size() == len(counts[bucket])
    assert {b: sorted(s) for b, s in seen.items()} == counts
    assert len(eng.finished) == B * len(counts)


@pytest.mark.parametrize("width,slots,groups,k", [
    (2, [7], [3], 1),
    (2, [0, 7], [0, 3], 2),
    (2, [1, 2, 4], None, 4),
    (1, [1], [1], 1),
    (1, [0, 5, 6], [0, 1, 5, 6], 4),
    (1, [0, 1, 2, 3, 4], None, 8),
    (4, [2, 3], [0], 1),
])
def test_dispatch_groups(width, slots, groups, k):
    """(d) The groups a chunk dispatches: those holding a prefilling slot,
    ascending, padded with the first empty groups up to a power of two;
    None once that is every group."""
    eng = _engine("no_codec")
    got, n = eng._dispatch_groups(slots, width)
    assert n == k
    assert (got if got is None else np.asarray(got).tolist()) == groups


def test_prefill_rows_count_dispatched_rows():
    """(d) ``prefill_rows`` grows by k * R * chunk_size per chunk, k the
    dispatched group count; ``prefill_tokens`` by the prompt tokens."""
    eng = _engine("paged_gqa")
    lengths = [1, C + 3, 3, 4, 5, 6, 7, 3 * C + 2]   # slots 0-7
    for u, n in enumerate(lengths):
        eng.submit(Request(uid=u, prompt=[1 + u] * n, max_new_tokens=2))
    eng._boundary()
    rows = []
    while eng._pending_prefill():
        before = eng.stats["prefill_rows"]
        eng._prefill_one_chunk()
        rows.append(eng.stats["prefill_rows"] - before)
    # chunk 0: every group; chunk 1: slots 1 and 7 (groups 0 and 3);
    # chunks 2-3: slot 7 alone (group 3)
    R = 2
    assert rows == [4 * R * C, 2 * R * C, 1 * R * C, 1 * R * C]
    assert eng.stats["prefill_tokens"] == sum(lengths)
    assert eng.stats["prefill_rows"] == (4 + 2 + 1 + 1) * R * C


def _served(eng):
    return {r.uid: r.out for r in eng.finished}


def _staggered(eng, prompts, max_new):
    """Group 0 (slots 0-3) admitted and decoding before group 1 arrives,
    so group 1 prefills alone while group 0 holds its slots."""
    for u in range(4):
        eng.submit(Request(uid=u, prompt=prompts[u], max_new_tokens=max_new))
    for _ in range(2):
        eng.tick()
    assert all(s.req is not None for s in eng.slots[:4])
    for u in range(4, 8):
        eng.submit(Request(uid=u, prompt=prompts[u], max_new_tokens=max_new))
    eng.run(max_steps=500)
    return _served(eng)


def test_staggered_admissions_match_arrivals_together():
    """(e) 8 slots under ``c3sl:R=4|int8``: four requests admitted and
    decoding before four more arrive emit the same tokens as the eight
    arriving together.  The groups are the same four slots either way, and
    the late group's chunks compute that group alone."""
    rng = np.random.RandomState(5)
    prompts = [list(map(int, rng.randint(1, 64, 2 * C + 3)))
               for _ in range(8)]
    kw = dict(codec="c3sl:R=4|int8", max_len=64)
    together = _engine("paged_gqa", **kw)
    for u in range(8):
        together.submit(Request(uid=u, prompt=prompts[u], max_new_tokens=12))
    together.run(max_steps=500)
    late = _engine("paged_gqa", **kw)
    rows = []
    dispatch = late._dispatch_groups

    def spy(slots, width):
        groups, k = dispatch(slots, width)
        rows.append(k)
        return groups, k

    late._dispatch_groups = spy
    assert _staggered(late, prompts, 12) == _served(together)
    assert len(together.finished) == 8
    assert rows.count(1) == 6           # each group's 3 chunks, alone


def test_ragged_staggered_tokens_match_full_dispatch():
    """(e) Ragged prompts arriving one by one, slots of one group mixing
    prefill and decode: the engine emits the same tokens, and ends with the
    same cache (to ``TOL``), as the same engine made to compute every group of every
    chunk."""
    rng = np.random.RandomState(9)
    prompts = [list(map(int, rng.randint(1, 64, n)))
               for n in (3, 17, 9, 26, 5, 12, 30, 2, 21, 7)]

    def serve(full):
        eng = _engine("paged_gqa", codec="c3sl:R=4|int8", max_len=64)
        if full:
            eng._dispatch_groups = lambda slots, width: (None,
                                                         B // width)
        for u, p in enumerate(prompts):
            eng.submit(Request(uid=u, prompt=p, max_new_tokens=6 + u % 5))
            eng.tick()
        eng.run(max_steps=1000)
        return _served(eng), jax.tree.map(np.asarray, eng.cache), eng.stats

    got, cache, stats = serve(False)
    want, cache_full, stats_full = serve(True)
    assert got == want and len(got) == len(prompts)
    _assert_trees_equal(cache, cache_full, TOL)
    assert stats["prefill_rows"] < stats_full["prefill_rows"]
    assert stats["prefill_tokens"] == stats_full["prefill_tokens"]
