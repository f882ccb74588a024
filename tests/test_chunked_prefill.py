"""Chunked prefill: token-level equivalence vs token-by-token decode, ragged
chunk tails, masked rows, ring buffers, stateful layers, and engine-level
equivalence (greedy, with and without the C3-SL codec)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs as codecs_lib
from repro.configs.base import get_config, reduced
from repro.models import lm as lm_lib
from repro.serving.engine import BatchedEngine, Request


def _cfg(**over):
    base = dict(num_layers=2, d_model=128, d_ff=256, vocab_size=128,
                num_heads=4, num_kv_heads=2, head_dim=32)
    base.update(over)
    return reduced(get_config("deepseek-7b"), **base)


def _decode_reference(params, cfg, prompts, T, codec=None, codec_params=None):
    """Token-by-token ingest with per-row positions; returns (logits at each
    row's last prompt token, final cache)."""
    B = len(prompts)
    cache = lm_lib.init_decode_cache(params, cfg, B, T)
    pos = np.zeros((B,), np.int64)
    ref = [None] * B
    for t in range(max(len(p) for p in prompts)):
        toks = np.array([[p[t] if t < len(p) else 0] for p in prompts], np.int32)
        lg, cache = lm_lib.decode_step(params, cache, jnp.asarray(toks),
                                       jnp.asarray(pos.astype(np.int32)), cfg,
                                       codec=codec, codec_params=codec_params)
        for b, p in enumerate(prompts):
            if t < len(p):
                pos[b] += 1
                if t == len(p) - 1:
                    ref[b] = np.asarray(lg[b, -1])
    return np.stack(ref), cache


def _chunked(params, cfg, prompts, T, C, codec=None, codec_params=None):
    """prefill_chunk over ceil(maxlen/C) chunks with ragged-tail masks;
    returns (per-row last-valid logits of the chunk each row completed in,
    final cache)."""
    B = len(prompts)
    cache = lm_lib.init_decode_cache(params, cfg, B, T)
    pos = jnp.zeros((B,), jnp.int32)
    out = np.zeros((B, cfg.vocab_size), np.float32)
    for k in range(math.ceil(max(len(p) for p in prompts) / C)):
        toks = np.zeros((B, C), np.int32)
        val = np.zeros((B, C), bool)
        for b, p in enumerate(prompts):
            seg = p[k * C:(k + 1) * C]
            if seg:
                toks[b, :len(seg)] = seg
                val[b, :len(seg)] = True
        lg, cache = lm_lib.prefill_chunk(params, cache, jnp.asarray(toks), pos,
                                         cfg, codec=codec,
                                         codec_params=codec_params,
                                         valid=jnp.asarray(val))
        pos = pos + jnp.asarray(val.sum(1), jnp.int32)
        for b, p in enumerate(prompts):
            if k * C < len(p) <= (k + 1) * C:
                out[b] = np.asarray(lg[b])
    return out, cache


def test_prefill_matches_decode_ragged_tails():
    """Ragged prompts (rows complete in different chunks): same last-token
    logits, same greedy token, and identical cache contents at every
    written position; positions past a row's prompt stay untouched."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 17, 23, 2, 9, 11, 40], [7, 3, 1, 19, 25]]
    ref, cache_ref = _decode_reference(params, cfg, prompts, 32)
    got, cache_new = _chunked(params, cfg, prompts, 32, C=4)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert (got.argmax(-1) == ref.argmax(-1)).all()
    k_ref = np.asarray(cache_ref["stack"]["l0_0_attn"]["k"])
    k_new = np.asarray(cache_new["stack"]["l0_0_attn"]["k"])
    for b, p in enumerate(prompts):
        np.testing.assert_allclose(k_new[:, b, :len(p)], k_ref[:, b, :len(p)],
                                   rtol=1e-4, atol=1e-5)
        # padded tail positions were dropped, not written
        assert np.abs(k_new[:, b, len(p):]).max() == 0.0


def test_prefill_matches_decode_with_c3sl_codec():
    """Per-position sequence grouping reproduces the decode path's batch-wise
    codec groups: same greedy tokens with c3sl:R=4|int8 at the cut layer."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    codec = codecs_lib.build("c3sl:R=4|int8", D=cfg.d_model)
    cp = codec.init(jax.random.PRNGKey(7))
    rng = np.random.RandomState(3)
    prompts = [list(map(int, rng.randint(1, cfg.vocab_size, 6)))
               for _ in range(4)]  # equal lengths: group contents match
    ref, _ = _decode_reference(params, cfg, prompts, 32, codec, cp)
    got, _ = _chunked(params, cfg, prompts, 32, C=4, codec=codec,
                      codec_params=cp)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def test_prefill_sliding_window_ring_buffer():
    """Prompt longer than the attention window: chunked prefill must match
    the decode loop through the ring-buffer cache."""
    import dataclasses
    cfg = dataclasses.replace(_cfg(), sliding_window=8)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(5)
    prompts = [list(map(int, rng.randint(1, cfg.vocab_size, 12)))
               for _ in range(2)]
    ref, _ = _decode_reference(params, cfg, prompts, 32)
    got, _ = _chunked(params, cfg, prompts, 32, C=4)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def test_prefill_mla_moe_first_dense():
    """MLA absorbed-matrices prefill + MoE + first-dense superblock."""
    cfg = reduced(get_config("deepseek-v2-lite-16b"))
    assert cfg.first_dense_layers
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(11)
    prompts = [list(map(int, rng.randint(1, cfg.vocab_size, 7))),
               list(map(int, rng.randint(1, cfg.vocab_size, 4)))]
    ref, _ = _decode_reference(params, cfg, prompts, 16)
    got, _ = _chunked(params, cfg, prompts, 16, C=4)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def test_prefill_stateful_rwkv():
    """Recurrent sublayers (token-shift + wkv state) advance inside the
    chunked program with masked commits."""
    cfg = reduced(get_config("rwkv6-1.6b"), d_model=128, d_ff=256,
                  vocab_size=128, num_heads=4)
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(13)
    prompts = [list(map(int, rng.randint(1, cfg.vocab_size, 7))),
               list(map(int, rng.randint(1, cfg.vocab_size, 5)))]
    ref, _ = _decode_reference(params, cfg, prompts, 16)
    got, _ = _chunked(params, cfg, prompts, 16, C=4)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def test_prefill_masked_rows_are_untouched():
    """A row with valid=False everywhere (mid-decode while another slot
    prefills) keeps its cache bit-identical."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    B, T = 2, 16
    cache = lm_lib.init_decode_cache(params, cfg, B, T)
    # give row 1 some history first
    for t in range(3):
        _, cache = lm_lib.decode_step(params, cache,
                                      jnp.asarray([[0], [7 + t]], jnp.int32),
                                      jnp.asarray([0, t], jnp.int32), cfg)
    before = jax.tree.map(np.asarray, cache)
    toks = np.zeros((B, 4), np.int32)
    toks[0] = [5, 6, 7, 8]
    val = np.zeros((B, 4), bool)
    val[0] = True
    _, cache = lm_lib.prefill_chunk(params, cache, jnp.asarray(toks),
                                    jnp.asarray([0, 3], jnp.int32), cfg,
                                    valid=jnp.asarray(val))
    after = jax.tree.map(np.asarray, cache)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        if a.ndim >= 2 and a.shape[1] == B:       # stacked (N, B, ...) leaves
            assert (a[:, 1] == b[:, 1]).all()


# ---------------------------------------------------------------------------
# engine-level equivalence (chunked prefill + device-resident decode windows
# vs a token-by-token greedy decode)
# ---------------------------------------------------------------------------

def _greedy_reference(params, cfg, prompts, max_new, *, width, max_len,
                      eos_id=None, codec=None, codec_params=None):
    """Greedy decode one token a step through ``lm.decode_step``: the
    prompts run as the rows of batches of ``width`` (the last padded with
    empty rows), each row fed its prompt, then its own argmax until EOS,
    its ``max_new`` or ``max_len``.  Finished and empty rows are masked
    out (``live``).  Returns each prompt's generated tokens."""
    step = jax.jit(lambda c, t, p, live: lm_lib.decode_step(
        params, c, t, p, cfg, codec=codec, codec_params=codec_params,
        live=live))
    outs = []
    for lo in range(0, len(prompts), width):
        rows = prompts[lo:lo + width]
        cache = lm_lib.init_decode_cache(params, cfg, width, max_len)
        pos = np.zeros((width,), np.int32)
        out = [[] for _ in range(width)]
        live = np.arange(width) < len(rows)
        while live.any():
            toks = np.zeros((width, 1), np.int32)
            for b, p in enumerate(rows):
                toks[b, 0] = p[pos[b]] if pos[b] < len(p) else out[b][-1]
            logits, cache = step(cache, jnp.asarray(toks), jnp.asarray(pos),
                                 jnp.asarray(live))
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            for b, p in enumerate(rows):
                if not live[b]:
                    continue
                pos[b] += 1
                if pos[b] >= len(p):
                    out[b].append(int(nxt[b]))
                    live[b] = not (nxt[b] == eos_id
                                   or len(out[b]) >= max_new[lo + b]
                                   or pos[b] >= max_len)
        outs += out[:len(rows)]
    return outs


@pytest.mark.parametrize("width", [1, 3])
def test_engine_equals_greedy_reference_ragged_recycling(width):
    """6 ragged requests through 3 slots (mid-flight recycling, chunk tails
    of every length, EOS): the engine emits the reference's greedy outputs
    exactly, whichever batch rows the reference runs them in."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = BatchedEngine(params, cfg, num_slots=3, max_len=32, eos_id=1,
                        chunk_size=4, sync_every=3)
    lens = [3, 5, 9, 2, 7, 4]
    rng = np.random.RandomState(17)
    reqs = [list(map(int, rng.randint(2, cfg.vocab_size, n))) for n in lens]
    max_new = [4 + u % 3 for u in range(len(reqs))]
    for u, p in enumerate(reqs):
        eng.submit(Request(uid=u, prompt=list(p), max_new_tokens=max_new[u]))
    got = {r.uid: r.out for r in eng.run()}
    ref = _greedy_reference(params, cfg, reqs, max_new, width=width,
                            max_len=32, eos_id=1)
    assert got == dict(enumerate(ref))


def test_engine_equals_greedy_reference_with_codec():
    """Full batch of equal-length prompts through the C3-SL codec: the
    per-position sequence groups coincide with a decode step's batch
    groups, so outputs match exactly."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    codec = codecs_lib.build("c3sl:R=4|int8", D=cfg.d_model)
    cp = codec.init(jax.random.PRNGKey(7))
    eng = BatchedEngine(params, cfg, num_slots=4, max_len=32, codec=codec,
                        codec_params=cp, chunk_size=4, sync_every=2)
    rng = np.random.RandomState(19)
    reqs = [list(map(int, rng.randint(1, cfg.vocab_size, 8))) for _ in range(4)]
    for u, p in enumerate(reqs):
        eng.submit(Request(uid=u, prompt=list(p), max_new_tokens=4))
    got = {r.uid: r.out for r in eng.run()}
    ref = _greedy_reference(params, cfg, reqs, [4] * 4, width=4, max_len=32,
                            codec=codec, codec_params=cp)
    assert got == dict(enumerate(ref))


@pytest.mark.parametrize("width", [1, 2])
def test_engine_prompt_longer_than_chunk_and_sync_window(width):
    """Prompt spanning many chunks + generation spanning many sync windows."""
    cfg = _cfg()
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = BatchedEngine(params, cfg, num_slots=2, max_len=64, chunk_size=4,
                        sync_every=5)
    prompt = list(range(2, 25))                    # 23 tokens -> 6 chunks
    eng.submit(Request(uid=0, prompt=list(prompt), max_new_tokens=12))
    ref = _greedy_reference(params, cfg, [prompt], [12], width=width,
                            max_len=64)
    assert [r.out for r in eng.run()] == ref
