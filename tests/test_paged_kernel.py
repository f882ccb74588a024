"""In-kernel paged attention vs the gather read: BIT-identical, loudly gated.

The kernel tier's contract (repro.kernels.paged_attention) is the same one
tests/test_paged_cache.py pins for paged-vs-contiguous: not "close", but
bit-for-bit equal greedy outputs — the page-table walk moved into the
kernel must be invisible to every downstream consumer.  Covered here:

- step-level decode equivalence across plain GQA, ring-buffer SWA, and
  int8-quantized KV, over SHUFFLED page tables with dead slots and
  staggered per-slot positions;
- engine-level greedy identity (``kv_read="kernel"`` vs ``"gather"``),
  including mid-stream eviction/resume under slot preemption;
- the ``gather_pages`` trailing-page parities (length exactly on a page
  boundary vs one-past — the edge audited in repro.models.paging);
- a hypothesis property for the in-kernel page-table addressing math;
- the LOUD gating: kernel-without-paged raises, uncovered layouts warn,
  and the effective execution mode is surfaced in engine stats.
"""
import dataclasses
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.models import attention as attn_lib
from repro.models import lm as lm_lib
from repro.models.paging import PagedLayout, gather_pages
from repro.serving.engine import BatchedEngine, Request


def _cfg(**over):
    base = dict(num_layers=2, d_model=128, d_ff=256, vocab_size=128,
                num_heads=4, num_kv_heads=2, head_dim=32)
    base.update(over)
    return reduced(get_config("deepseek-7b"), **base)


def _variant_cfg(variant):
    cfg = _cfg()
    if variant == "swa":
        cfg = dataclasses.replace(cfg, sliding_window=8)
    elif variant == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    return cfg


def _paged_cache(params, cfg, B, T, ps, rng):
    """Fully-provisioned paged cache with SHUFFLED page tables (same
    construction as tests/test_paged_cache.py): the kernel's in-table walk
    can only agree with gather if the indirection is right."""
    pps = -(-T // ps)
    len_swa = min(T, cfg.sliding_window) if cfg.sliding_window else 0
    pps_swa = -(-len_swa // ps) if len_swa else 0
    layout = PagedLayout(ps, T, B * pps, len_swa, max(B * pps_swa, 1)
                         if len_swa else 0)
    cache = lm_lib.init_decode_cache(params, cfg, B, T, paged=layout)
    cache["pages"] = jnp.asarray(
        rng.permutation(B * pps).astype(np.int32).reshape(B, pps))
    if len_swa:
        cache["pages_swa"] = jnp.asarray(
            rng.permutation(B * pps_swa).astype(np.int32).reshape(B, pps_swa))
    return layout, cache


@pytest.fixture(scope="module", params=["plain", "swa", "int8"])
def variant_setup(request):
    cfg = _variant_cfg(request.param)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    return request.param, cfg, params


# ---------------------------------------------------------------------------
# step-level: decode_step(kv_read="kernel") == decode_step(kv_read="gather")
# ---------------------------------------------------------------------------

def test_decode_step_kernel_bitwise_equals_gather(variant_setup):
    _, cfg, params = variant_setup
    B, T, ps = 4, 32, 8
    rng = np.random.RandomState(1)
    layout, cache = _paged_cache(params, cfg, B, T, ps, rng)
    cache_g = dict(cache)
    cache_k = dict(cache)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 1)), jnp.int32)
    # staggered per-slot positions + a dead slot: the kernel must honor
    # the same per-row masks, not just a uniform clock
    pos = np.array([0, 3, 1, 5], np.int32)
    live = jnp.array([True, True, False, True])
    for _ in range(6):
        lg, cache_g = lm_lib.decode_step(params, cache_g, toks,
                                         jnp.asarray(pos), cfg, paged=layout,
                                         live=live, kv_read="gather")
        lk, cache_k = lm_lib.decode_step(params, cache_k, toks,
                                         jnp.asarray(pos), cfg, paged=layout,
                                         live=live, kv_read="kernel")
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lk))
        for g, k in zip(jax.tree.leaves(cache_g), jax.tree.leaves(cache_k)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(k))
        toks = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        pos = pos + np.asarray(live)


# ---------------------------------------------------------------------------
# kernel-level: trailing-page parity (the audited gather_pages edge)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [16, 17, 23])
def test_trailing_page_parity_matches_sdpa_over_gather(length):
    """length = 16 sits EXACTLY on the page boundary (2 full pages of 8);
    17 is one-past (3rd page holds one row); 23 is a ragged tail.  The
    kernel fetches whole pages and slices scratch, gather slices the
    reshaped view — both must agree bitwise with the gather read's decode
    attention, with the causal mask (not
    the slice) hiding unwritten positions either way."""
    from repro.kernels import paged_attention as pa
    B, ps, H, KV, hd = 3, 8, 4, 2, 16
    P = -(-length // ps)
    rng = np.random.RandomState(0)
    npages = B * P + 2                     # spare pages: tables don't cover pool
    k_pool = jnp.asarray(rng.randn(npages, ps, KV, hd).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(npages, ps, KV, hd).astype(np.float32))
    table = jnp.asarray(rng.permutation(npages)[:B * P].astype(np.int32)
                        .reshape(B, P))
    q = jnp.asarray(rng.randn(B, 1, H, hd).astype(np.float32))
    # pos on both sides of the last boundary, incl. the final position
    pos = jnp.asarray(np.array([length - 1, length - 2,
                                max(length - ps - 1, 0)], np.int32))
    got = pa.paged_attention(q, k_pool, v_pool, table, pos, length=length)

    view = {"k": gather_pages(k_pool, table, length),   # (B, T, KV, hd)
            "v": gather_pages(v_pool, table, length)}
    want = attn_lib.sdpa_decode(q, view, pos, length)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# hypothesis property: in-kernel page-table addressing math
# ---------------------------------------------------------------------------

@pytest.mark.property
def test_page_walk_addressing_property():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, strategies as st
    from repro.kernels import paged_attention as pa

    @given(st.data())
    def run(data):
        ps = data.draw(st.integers(1, 8), label="page_size")
        P = data.draw(st.integers(1, 4), label="pages_per_slot")
        B = data.draw(st.integers(1, 3), label="batch")
        length = data.draw(st.integers(1, P * ps), label="length")
        seed = data.draw(st.integers(0, 2 ** 16), label="seed")
        H, KV, hd = 2, 2, 4
        rng = np.random.RandomState(seed)
        npages = B * P
        k_pool = jnp.asarray(rng.randn(npages, ps, KV, hd).astype(np.float32))
        v_pool = jnp.asarray(rng.randn(npages, ps, KV, hd).astype(np.float32))
        table = jnp.asarray(rng.permutation(npages).astype(np.int32)
                            .reshape(B, P))
        q = jnp.asarray(rng.randn(B, 1, H, hd).astype(np.float32))
        pos = jnp.asarray(rng.randint(0, length, (B,)).astype(np.int32))
        got = pa.paged_attention(q, k_pool, v_pool, table, pos, length=length)
        view = {"k": gather_pages(k_pool, table, length),
                "v": gather_pages(v_pool, table, length)}
        want = attn_lib.sdpa_decode(q, view, pos, length)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    run()


# ---------------------------------------------------------------------------
# engine-level: greedy outputs identical across kv_read, incl. preemption
# ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 32)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("sync_every", 4)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", 8)
    with warnings.catch_warnings():
        # kv_read="kernel" warns about its gather fallbacks by design
        # (tested explicitly below); keep equivalence runs quiet
        warnings.simplefilter("ignore")
        return BatchedEngine(params, cfg, greedy=True, seed=0, **kw)


def _prompts(rng, lens, vocab=128):
    return [[int(t) for t in rng.randint(1, vocab, n)] for n in lens]


def test_engine_greedy_identity_kernel_vs_gather(variant_setup):
    _, cfg, params = variant_setup
    rng = np.random.RandomState(7)
    # prompt lengths straddle the page boundary (8): 7 / 8 / 9 cover both
    # trailing-page parities through prefill-then-decode
    prompts = _prompts(rng, [7, 8, 9, 3], vocab=cfg.vocab_size)
    outs = {}
    for kv_read in ("gather", "kernel"):
        eng = _engine(cfg, params, kv_read=kv_read)
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid=uid, prompt=list(p), max_new_tokens=8))
        outs[kv_read] = {r.uid: r.out for r in eng.run()}
    assert outs["kernel"] == outs["gather"]
    assert len(outs["kernel"]) == len(prompts)


def test_engine_kernel_survives_eviction_and_resume():
    """Mid-stream eviction/resume (slot preemption) under the kernel read:
    the re-admitted request re-prefills and resumes to the same greedy
    output as an uncontended gather-read run."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    shorts = [Request(uid=i, prompt=_prompts(rng, [4])[0], max_new_tokens=8)
              for i in range(2)]
    premium = Request(uid=9, prompt=_prompts(rng, [20])[0], max_new_tokens=4,
                      priority=1)
    # solo (uncontended) references on the GATHER path
    ref = {}
    for r in shorts + [premium]:
        eng = _engine(cfg, params, num_slots=2, num_pages=6, kv_read="gather",
                      preemption=True)
        eng.submit(Request(uid=r.uid, prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens))
        ref[r.uid] = eng.run()[0].out
    # oversubscribed KERNEL-read engine: premium preempts the shorts
    eng = _engine(cfg, params, num_slots=2, num_pages=6, kv_read="kernel",
                  preemption=True)
    for r in shorts:
        eng.submit(r)
    eng.tick()
    eng.submit(premium)
    done = {r.uid: r for r in eng.run()}
    assert set(done) == {0, 1, 9}
    assert eng.stats["evictions"] >= 1
    for uid, r in done.items():
        assert r.out == ref[uid], (uid, r.evictions)


# ---------------------------------------------------------------------------
# loud gating + execution-mode surfacing
# ---------------------------------------------------------------------------

def test_kernel_requires_paged_layout():
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="requires kv_layout='paged'"):
        BatchedEngine(params, cfg, kv_layout="contiguous", kv_read="kernel")


def test_kernel_requires_attn_layers():
    cfg = _cfg()
    cfg = dataclasses.replace(cfg, block_pattern=(("mamba", "mlp"),))
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="no attn sublayer"):
        BatchedEngine(params, cfg, kv_layout="paged", kv_read="kernel")


def test_apply_gqa_decode_rejects_kernel_without_pages():
    with pytest.raises(ValueError, match="requires the paged cache layout"):
        attn_lib.apply_gqa_decode(
            {}, jnp.zeros((2, 1, 128)), {}, jnp.zeros((2,), jnp.int32),
            num_heads=4, num_kv_heads=2, head_dim=32, rotary_dim=32,
            kv_read="kernel")


def test_fallback_warning_is_loud():
    """Uncovered reads (here: chunked prefill) warn at construction —
    the engine never silently serves gather while claiming the kernel."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    with pytest.warns(UserWarning, match="stay on the gather read path"):
        BatchedEngine(params, cfg, kv_layout="paged", kv_read="kernel")


def test_execution_mode_in_engine_stats():
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = _engine(cfg, params, kv_read="kernel")
    assert eng.stats["kv_read"] == "kernel"
    expected = ("pallas-compiled" if jax.default_backend() == "tpu"
                else "pallas-interpret")
    assert eng.stats["kv_read_execution_mode"] == expected
    assert eng.stats["codec_execution_mode"] == "none"

    eng = _engine(cfg, params, kv_read="gather", codec="c3sl:R=2")
    assert eng.stats["kv_read"] == "gather"
    assert eng.stats["kv_read_execution_mode"] == "gather"
    assert eng.stats["codec_execution_mode"] == "fft"
