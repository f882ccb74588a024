"""The stacked cache rides the layer scan and is updated in place, on both
sides of the C3-SL cut.

``decode_step`` and ``prefill_chunk`` with a codec run the layers before
the cut and the layers after it over ONE stacked cache: paged pools are
addressed as layer-flattened pools behind page tables offset by
``l * num_pages``, other leaves are read and written at their layer index.
Each case here compares them, step by step, with a per-layer reference
kept in this file: slice layer ``l``'s weights and cache out of the stack,
run ``apply_superblock_decode`` / ``apply_superblock_prefill`` on them with
the un-offset page tables, and write the layer's cache back.  Logits and
every cache leaf must be bit-identical, for every cache layout the engine
builds.  A second test compiles the serving programs for a described TPU
v5e and checks that no pool-sized copy is left in them.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs as codecs_lib
from repro.codecs.c3sl import sequence_group_decode, sequence_group_encode
from repro.configs.base import get_config, reduced
from repro.models import lm as lm_lib
from repro.models import stack as stack_lib
from repro.models.paging import PagedLayout

B, T, PS, C = 4, 16, 4, 8

CASES = {
    "paged_kernel": ("deepseek-7b", True, "kernel", {}),
    "paged_gather": ("deepseek-7b", True, "gather", {}),
    "int8": ("deepseek-7b", True, "kernel", {"kv_cache_quant": True}),
    "swa": ("deepseek-7b", True, "kernel", {"sliding_window": 8}),
    "mla": ("deepseek-v2-lite-16b", True, "gather", {}),
    "contiguous": ("deepseek-7b", False, "gather", {}),
    # mamba state beside a paged attention pool in one superblock
    "recurrent": ("jamba-1.5-large-398b", True, "gather",
                  {"block_pattern": (("attn", "mlp"), ("mamba", "mlp"))}),
}


def _cfg(arch, over):
    base = reduced(get_config(arch), d_model=64, num_heads=4, head_dim=16,
                   d_ff=128, vocab_size=64, **over)
    # four superblocks: two on each side of the cut, so the scans index
    # layers 0-1 and 2-3 of one stack
    n = 4 * len(base.block_pattern) + base.first_dense_layers
    return dataclasses.replace(base, num_layers=n)


def _setup(case):
    arch, paged, kv_read, over = CASES[case]
    cfg = _cfg(arch, over)
    assert cfg.num_superblocks == 4
    params = lm_lib.init_lm_params(jax.random.PRNGKey(0), cfg)
    codec = codecs_lib.build("c3sl:R=2", D=cfg.d_model)
    cp = codec.init(jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    layout = None
    if paged:
        pps = T // PS
        len_swa = min(T, cfg.sliding_window) if cfg.sliding_window else 0
        pps_swa = len_swa // PS
        layout = PagedLayout(PS, T, B * pps, len_swa, B * pps_swa)
    cache = lm_lib.init_decode_cache(params, cfg, B, T, paged=layout)
    # fill every leaf with noise, so a write to the wrong layer or page,
    # or a read of one, cannot hide behind zeros
    cache = {k: (jax.tree.map(
        lambda a: jnp.asarray(rng.randn(*a.shape) * 3).astype(a.dtype), v)
        if k in ("stack", "first") else v) for k, v in cache.items()}
    if paged:
        cache["pages"] = jnp.asarray(
            rng.permutation(B * pps).astype(np.int32).reshape(B, pps))
        if layout.len_swa:
            cache["pages_swa"] = jnp.asarray(rng.permutation(
                B * pps_swa).astype(np.int32).reshape(B, pps_swa))
    return cfg, params, codec, cp, layout, kv_read, cache, rng


def _per_layer(params, stack, layers, run, h):
    """The reference layer loop: slice, apply, write back."""
    for l in layers:
        p_l = jax.tree.map(lambda a: a[l], params["stack"])
        c_l = jax.tree.map(lambda a: a[l], stack)
        h, new = run(p_l, c_l, h)
        stack = jax.tree.map(lambda a, n: a.at[l].set(n), stack, new)
    return h, stack


def _ref_decode(params, cache, tokens, pos, cfg, codec, cp, paged, live,
                kv_read):
    h = params["embed"][tokens]
    kw = dict(paged=paged, pages=cache.get("pages"),
              pages_swa=cache.get("pages_swa"), live=live)
    new = dict(cache)
    if cfg.first_dense_layers:
        h, new["first"] = stack_lib.apply_superblock_decode(
            params["first"], cache["first"], cfg, h, pos, **kw)

    def run(p, c, h):
        return stack_lib.apply_superblock_decode(p, c, cfg, h, pos,
                                                 kv_read=kv_read, **kw)

    n_cut, n = cfg.num_superblocks // 2, cfg.num_superblocks
    h, st = _per_layer(params, cache["stack"], range(n_cut), run, h)
    h = jnp.where(live[:, None, None], h, 0.0)
    d = h.shape[-1]
    h = codec.decode(cp, codec.encode(cp, h.reshape(B, d))).reshape(B, 1, d)
    h, new["stack"] = _per_layer(params, st, range(n_cut, n), run, h)
    h = stack_lib._apply_norm(cfg, params["final_norm"], h)
    return h @ params["head"], new


def _ref_prefill(params, cache, tokens, pos, cfg, codec, cp, paged, valid):
    h = params["embed"][tokens]
    kw = dict(paged=paged, pages=cache.get("pages"),
              pages_swa=cache.get("pages_swa"))
    new = dict(cache)
    if cfg.first_dense_layers:
        h, new["first"] = stack_lib.apply_superblock_prefill(
            params["first"], cache["first"], cfg, h, pos, valid, **kw)

    def run(p, c, h):
        return stack_lib.apply_superblock_prefill(p, c, cfg, h, pos, valid,
                                                  **kw)

    n_cut, n = cfg.num_superblocks // 2, cfg.num_superblocks
    h, st = _per_layer(params, cache["stack"], range(n_cut), run, h)
    h = jnp.where(valid[:, :, None], h, 0.0)
    payload = sequence_group_encode(codec, cp, h.swapaxes(0, 1))
    h = sequence_group_decode(codec, cp, payload, C, B).swapaxes(0, 1)
    h, new["stack"] = _per_layer(params, st, range(n_cut, n), run, h)
    last = jnp.maximum(valid.sum(-1).astype(jnp.int32) - 1, 0)
    h = stack_lib._apply_norm(cfg, params["final_norm"], h[jnp.arange(B), last])
    return h @ params["head"], new


def _assert_same(got, ref):
    (lg, cg), (lr, cr) = got, ref
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lr))
    flat_g = jax.tree_util.tree_flatten_with_path(cg)[0]
    flat_r = jax.tree.leaves(cr)
    assert len(flat_g) == len(flat_r)
    for (path, g), r in zip(flat_g, flat_r):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("case", list(CASES))
def test_cut_updates_stacked_cache_in_place(case):
    """One ragged prefill chunk, then live-masked decode steps at staggered
    positions: the in-place scan equals the per-layer reference."""
    cfg, params, codec, cp, paged, kv_read, cache, rng = _setup(case)
    prefill = jax.jit(lambda p, c, t, pos, v: lm_lib.prefill_chunk(
        p, c, t, pos, cfg, codec=codec, codec_params=cp, valid=v,
        paged=paged))
    ref_prefill = jax.jit(lambda p, c, t, pos, v: _ref_prefill(
        p, c, t, pos, cfg, codec, cp, paged, v))
    decode = jax.jit(lambda p, c, t, pos, live: lm_lib.decode_step(
        p, c, t, pos, cfg, codec=codec, codec_params=cp, paged=paged,
        live=live, kv_read=kv_read if paged else "gather"))
    ref_decode = jax.jit(lambda p, c, t, pos, live: _ref_decode(
        p, c, t, pos, cfg, codec, cp, paged, live,
        kv_read if paged else "gather"))

    pos = jnp.asarray([0, 2, 5, 1], jnp.int32)
    toks = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, C)), jnp.int32)
    valid = jnp.asarray(np.arange(C)[None] < np.array([[8], [3], [0], [6]]))
    got = prefill(params, cache, toks, pos, valid)
    _assert_same(got, ref_prefill(params, cache, toks, pos, valid))
    cache = got[1]
    pos = pos + valid.sum(-1).astype(jnp.int32)
    for step in range(3):
        toks = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, 1)), jnp.int32)
        live = jnp.asarray(np.arange(B) != step)
        got = decode(params, cache, toks, pos, live)
        _assert_same(got, ref_decode(params, cache, toks, pos, live))
        cache = got[1]
        pos = pos + live.astype(jnp.int32)
