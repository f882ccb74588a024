"""Superblock layer-stack engine.

The layer stack of every architecture is `num_superblocks` repetitions of
`cfg.block_pattern` (a tuple of layers, each a tuple of sublayer kinds).
Parameters for one superblock are a flat dict keyed "l{layer}_{idx}_{kind}";
the full stack stacks every leaf with a leading superblock axis and runs
`jax.lax.scan` over it (with remat in training), which keeps the HLO size
independent of depth — essential for the 88-layer dry-runs.

Sublayer kinds: attn, mla, mlp, moe, mamba, rwkv_tm, rwkv_cm, cross.
Every sublayer is pre-norm residual: h = h + f(norm(h)).

Serving (decode and chunked prefill) scans the layer index instead: the
stacked cache leaves ride the scan carry and each layer updates its own
part of them in place — a paged pool ``(L, N, ps, ...)`` as the
layer-flattened pool ``(L*N, ps, ...)`` behind page tables offset by
``l*N``, any other leaf at index ``l`` (see ``_scan_layers``).  A range of
layers (one side of the C3-SL cut) runs on the same stacked weights and
cache, so nothing is split at the cut or concatenated after it.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models.layers import apply_mlp, init_mlp, layer_norm, rms_norm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_norm(cfg: ModelConfig, dtype):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    return {"scale": jnp.ones((cfg.d_model,), dtype)}


def _apply_norm(cfg: ModelConfig, p, x):
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def init_sublayer(rng, kind: str, cfg: ModelConfig, dtype, *, dense_mlp: bool = False):
    """Params for one sublayer, including its pre-norm."""
    p: dict[str, Any] = {"norm": _init_norm(cfg, dtype)}
    if kind == "attn" or kind == "cross":
        p.update(attn_lib.init_gqa(rng, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                                   cfg.head_dim_, cfg.qkv_bias, dtype))
    elif kind == "mla":
        p.update(attn_lib.init_mla(rng, cfg.d_model, cfg.num_heads,
                                   kv_lora_rank=cfg.kv_lora_rank,
                                   qk_nope_dim=cfg.qk_nope_dim,
                                   qk_rope_dim=cfg.qk_rope_dim,
                                   v_head_dim=cfg.v_head_dim, dtype=dtype))
    elif kind == "mlp":
        p.update(init_mlp(rng, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype))
    elif kind == "moe" and dense_mlp:
        p.update(init_mlp(rng, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype))
    elif kind == "moe":
        p.update(moe_lib.init_moe(rng, cfg.d_model, cfg.moe_d_ff or cfg.d_ff,
                                  cfg.num_experts,
                                  num_shared_experts=cfg.num_shared_experts,
                                  dtype=dtype))
    elif kind == "mamba":
        p.update(mamba_lib.init_mamba(rng, cfg.d_model, cfg.d_inner,
                                      d_state=cfg.d_state, d_conv=cfg.d_conv,
                                      dtype=dtype))
    elif kind == "rwkv_tm":
        p.update(rwkv_lib.init_rwkv_timemix(rng, cfg.d_model, cfg.num_heads, dtype=dtype))
    elif kind == "rwkv_cm":
        p.update(rwkv_lib.init_rwkv_channelmix(rng, cfg.d_model, cfg.d_ff, dtype=dtype))
    else:
        raise ValueError(kind)
    return p


def init_superblock(rng, cfg: ModelConfig, dtype, *, pattern=None, dense_mlp=False):
    pattern = pattern or cfg.block_pattern
    p = {}
    for li, layer in enumerate(pattern):
        for si, kind in enumerate(layer):
            rng, sub = jax.random.split(rng)
            p[f"l{li}_{si}_{kind}"] = init_sublayer(sub, kind, cfg, dtype,
                                                    dense_mlp=dense_mlp)
    return p


def init_stack(rng, cfg: ModelConfig, dtype):
    """Stacked superblock params: every leaf has leading dim num_superblocks."""
    rngs = jax.random.split(rng, cfg.num_superblocks)
    return jax.vmap(lambda r: init_superblock(r, cfg, dtype))(rngs)


# ---------------------------------------------------------------------------
# apply (train / prefill)
# ---------------------------------------------------------------------------

def apply_sublayer(kind: str, p, cfg: ModelConfig, h, positions, *,
                   memory=None, sliding_window=None):
    """Returns (residual_update, aux_loss)."""
    x = _apply_norm(cfg, p["norm"], h)
    aux = jnp.array(0.0, jnp.float32)
    if kind == "attn":
        y = attn_lib.apply_gqa(p, x, positions, num_heads=cfg.num_heads,
                               num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
                               rotary_dim=cfg.rotary_dim, rope_theta=cfg.rope_theta,
                               sliding_window=sliding_window)
    elif kind == "mla":
        y = attn_lib.apply_mla(p, x, positions, num_heads=cfg.num_heads,
                               kv_lora_rank=cfg.kv_lora_rank,
                               qk_nope_dim=cfg.qk_nope_dim,
                               qk_rope_dim=cfg.qk_rope_dim,
                               v_head_dim=cfg.v_head_dim,
                               rope_theta=cfg.rope_theta,
                               sliding_window=sliding_window)
    elif kind == "cross":
        y = attn_lib.apply_cross_attention(p, x, memory, num_heads=cfg.num_heads,
                                           num_kv_heads=cfg.num_kv_heads,
                                           head_dim=cfg.head_dim_)
    elif kind == "mlp":
        y = apply_mlp(p, x)
    elif kind == "moe":
        if "router" in p:
            y, aux = moe_lib.apply_moe(p, x, top_k=cfg.experts_per_token,
                                       capacity_factor=cfg.capacity_factor)
        else:  # first_dense_layers replacement
            y = apply_mlp(p, x)
    elif kind == "mamba":
        y = mamba_lib.apply_mamba(p, x, d_state=cfg.d_state)
    elif kind == "rwkv_tm":
        y = rwkv_lib.apply_rwkv_timemix(p, x, num_heads=cfg.num_heads,
                                        mode=cfg.rwkv_mode)
    elif kind == "rwkv_cm":
        y = rwkv_lib.apply_rwkv_channelmix(p, x)
    else:
        raise ValueError(kind)
    return y, aux


def apply_superblock(p_sb, cfg: ModelConfig, h, positions, *, pattern=None,
                     memory=None, sliding_window=None):
    pattern = pattern or cfg.block_pattern
    aux_total = jnp.array(0.0, jnp.float32)
    for li, layer in enumerate(pattern):
        for si, kind in enumerate(layer):
            y, aux = apply_sublayer(kind, p_sb[f"l{li}_{si}_{kind}"], cfg, h,
                                    positions, memory=memory,
                                    sliding_window=sliding_window)
            h = h + y
            aux_total = aux_total + aux
    return h, aux_total


def _activation_constraint(h):
    """Sequence-shard the residual stream stored at superblock boundaries
    (Megatron-SP style): (B, S, D) -> P(batch_axes, "model", None).  The
    attention/mixer internals re-gather as needed; what matters is that the
    per-layer *stored* copies (the remat scan carries) are sharded, or the
    88-layer models blow past HBM.  No-op outside a (data, model) mesh or on
    non-divisible shapes."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or h.ndim != 3:
        return h
    from jax.sharding import AxisType, PartitionSpec as P
    # only axes still under automatic partitioning (inside shard_map some
    # axes are Manual and must not appear in constraints)
    names = {n for n, t in zip(am.axis_names, am.axis_types)
             if t != AxisType.Manual}
    if "model" not in names or "data" not in names:
        return h
    batch_ax = ("pod", "data") if "pod" in names else ("data",)
    bsz = 1
    for a in batch_ax:
        bsz *= am.shape[a]
    B, S, _ = h.shape
    if B % bsz or S % am.shape["model"]:
        return h
    return jax.lax.with_sharding_constraint(h, P(batch_ax, "model", None))


def apply_stack(stacked, cfg: ModelConfig, h, positions, *, memory=None,
                sliding_window=None, remat: bool = True):
    """Scan over superblocks.  Returns (h, total_aux_loss)."""

    def body(carry, p_sb):
        h, aux = carry
        h, a = apply_superblock(p_sb, cfg, h, positions, memory=memory,
                                sliding_window=sliding_window)
        # constrain the carry OUTPUT: this is the tensor lax.scan saves per
        # iteration for the backward pass — it must be sequence-sharded or
        # deep models blow past HBM (see DESIGN.md §distribution)
        h = _activation_constraint(h)
        return (h, aux + a), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (h, aux), _ = jax.lax.scan(body, (h, jnp.array(0.0, jnp.float32)), stacked)
    return h, aux


# ---------------------------------------------------------------------------
# decode (one token, stacked caches)
# ---------------------------------------------------------------------------

def init_sublayer_cache(kind: str, cfg: ModelConfig, batch: int, length: int,
                        dtype, *, paged=None):
    """One sublayer's decode cache.  With ``paged`` (a PagedLayout), the
    per-position kinds (attn/mla) become shared page POOLS
    (num_pages, page_size, ...) instead of per-slot (B, T, ...) strips —
    the same leaf constructors, re-dimensioned.  Stateful kinds
    (mamba/rwkv) keep their per-slot O(1) state either way."""
    if kind == "attn":
        if paged is not None:
            np_, sw = ((paged.num_pages_swa, True) if cfg.sliding_window
                       else (paged.num_pages, False))
            return attn_lib.init_gqa_cache(np_, paged.page_size,
                                           cfg.num_kv_heads, cfg.head_dim_,
                                           dtype, quant=cfg.kv_cache_quant)
        T = min(length, cfg.sliding_window) if cfg.sliding_window else length
        return attn_lib.init_gqa_cache(batch, T, cfg.num_kv_heads, cfg.head_dim_,
                                       dtype, quant=cfg.kv_cache_quant)
    if kind == "mla":
        if paged is not None:
            return attn_lib.init_mla_cache(paged.num_pages, paged.page_size,
                                           cfg.kv_lora_rank, cfg.qk_rope_dim,
                                           dtype)
        return attn_lib.init_mla_cache(batch, length, cfg.kv_lora_rank,
                                       cfg.qk_rope_dim, dtype)
    if kind == "mamba":
        return mamba_lib.init_mamba_state(batch, cfg.d_inner, d_state=cfg.d_state,
                                          d_conv=cfg.d_conv, dtype=dtype)
    if kind == "rwkv_tm":
        hd = cfg.d_model // cfg.num_heads
        return {"wkv": jnp.zeros((batch, cfg.num_heads, hd, hd), jnp.float32),
                "x_prev": jnp.zeros((batch, cfg.d_model), dtype)}
    if kind == "rwkv_cm":
        return {"x_prev": jnp.zeros((batch, cfg.d_model), dtype)}
    return {}  # mlp / moe / cross are stateless (cross re-reads memory)


def init_superblock_cache(cfg: ModelConfig, batch: int, length: int, dtype,
                          pattern=None, *, paged=None):
    pattern = pattern or cfg.block_pattern
    return {f"l{li}_{si}_{kind}": init_sublayer_cache(kind, cfg, batch, length,
                                                      dtype, paged=paged)
            for li, layer in enumerate(pattern)
            for si, kind in enumerate(layer)}


def init_stack_cache(cfg: ModelConfig, batch: int, length: int, dtype, *,
                     paged=None):
    one = init_superblock_cache(cfg, batch, length, dtype, paged=paged)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.num_superblocks, *a.shape)), one)


def _paged_args(kind: str, cfg: ModelConfig, paged, pages, pages_swa):
    """(pages, length) kwargs for an attn/mla sublayer: SWA attn caches use
    the ring table + window length, everything else the full-length table."""
    if paged is None:
        return {"pages": None, "length": None}
    if kind == "attn" and cfg.sliding_window:
        return {"pages": pages_swa, "length": paged.len_swa}
    return {"pages": pages, "length": paged.len_linear}


def apply_sublayer_decode(kind: str, p, cache, cfg: ModelConfig, h, pos, *,
                          memory=None, paged=None, pages=None, pages_swa=None,
                          live=None, kv_read="gather"):
    x = _apply_norm(cfg, p["norm"], h)
    if kind == "attn":
        # kv_read="kernel" only reaches GQA decode on the paged layout;
        # MLA (below) and every prefill path stay on the gather read —
        # the serving engine warns about those fallbacks up front.
        y, new_cache = attn_lib.apply_gqa_decode(
            p, x, cache, pos, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
            rotary_dim=cfg.rotary_dim, rope_theta=cfg.rope_theta,
            sliding_window=cfg.sliding_window, live=live,
            kv_read=kv_read if paged is not None else "gather",
            **_paged_args(kind, cfg, paged, pages, pages_swa))
    elif kind == "mla":
        y, new_cache = attn_lib.apply_mla_decode(
            p, x, cache, pos, num_heads=cfg.num_heads,
            kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
            qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta, live=live,
            **_paged_args(kind, cfg, paged, pages, pages_swa))
    elif kind == "cross":
        y = attn_lib.apply_cross_attention(p, x, memory, num_heads=cfg.num_heads,
                                           num_kv_heads=cfg.num_kv_heads,
                                           head_dim=cfg.head_dim_)
        new_cache = cache
    elif kind == "mlp":
        y, new_cache = apply_mlp(p, x), cache
    elif kind == "moe":
        if "router" in p:
            # decode: capacity = all tokens (dropping a decode token is a
            # user-visible quality bug, so serving never drops)
            y, _ = moe_lib.apply_moe(p, x, top_k=cfg.experts_per_token,
                                     capacity_factor=float(cfg.num_experts))
        else:
            y = apply_mlp(p, x)
        new_cache = cache
    elif kind == "mamba":
        y, new_cache = mamba_lib.apply_mamba_decode(p, x, cache, d_state=cfg.d_state)
    elif kind == "rwkv_tm":
        st = {"wkv": cache["wkv"], "x_prev_tm": cache["x_prev"]}
        y, st = rwkv_lib.apply_rwkv_timemix_decode(p, x, st, num_heads=cfg.num_heads)
        new_cache = {"wkv": st["wkv"], "x_prev": st["x_prev_tm"]}
    elif kind == "rwkv_cm":
        st = {"x_prev_cm": cache["x_prev"]}
        y, st = rwkv_lib.apply_rwkv_channelmix_decode(p, x, st)
        new_cache = {"x_prev": st["x_prev_cm"]}
    else:
        raise ValueError(kind)
    if live is not None and kind in ("mamba", "rwkv_tm", "rwkv_cm"):
        # recurrent state commits only for live rows (a mid-prefill slot's
        # state must not advance on interleaved decode steps)
        new_cache = jax.tree.map(
            lambda n, o: jnp.where(
                live.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new_cache, cache)
    return y, new_cache


def apply_superblock_decode(p_sb, cache_sb, cfg: ModelConfig, h, pos, *,
                            pattern=None, memory=None, paged=None, pages=None,
                            pages_swa=None, live=None, kv_read="gather"):
    pattern = pattern or cfg.block_pattern
    new_cache = {}
    for li, layer in enumerate(pattern):
        for si, kind in enumerate(layer):
            key = f"l{li}_{si}_{kind}"
            y, new_cache[key] = apply_sublayer_decode(
                kind, p_sb[key], cache_sb[key], cfg, h, pos, memory=memory,
                paged=paged, pages=pages, pages_swa=pages_swa, live=live,
                kv_read=kv_read)
            h = h + y
    return h, new_cache


def is_pool(kind: str, paged) -> bool:
    """Whether a sublayer's cache leaves are page pools shared by every slot
    (attn/mla on the paged layout, as :func:`init_sublayer_cache` builds
    them) rather than per-slot leaves."""
    return paged is not None and kind in ("attn", "mla")


def _layer_view(leaves, l, pooled: bool):
    """One sublayer's cache at layer ``l`` of the stacked cache.  A pool
    ``(L, N, ps, ...)`` is read as the layer-flattened pool
    ``(L*N, ps, ...)``, a free reshape of a row-major array that the page
    tables address once offset by ``l*N``; any other leaf ``(L, B, ...)``
    is indexed at ``l``."""
    if pooled:
        return {n: a.reshape(-1, *a.shape[2:]) for n, a in leaves.items()}
    return {n: jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
            for n, a in leaves.items()}


def _put_layer(leaves, new, l, pooled: bool):
    """Store what :func:`_layer_view` handed out back in the stack: a pool's
    writes landed in place, so it only takes its stacked shape again; any
    other leaf is written at layer ``l``."""
    if pooled:
        return {n: new[n].reshape(a.shape) for n, a in leaves.items()}
    return {n: jax.lax.dynamic_update_index_in_dim(a, new[n], l, 0)
            for n, a in leaves.items()}


def _scan_layers(stacked, cache, cfg: ModelConfig, h, layers, apply_sb,
                 paged, pages, pages_swa):
    """Run ``apply_sb(p_sb, cache_sb, h, pages, pages_swa)`` for each layer
    ``l`` in ``layers`` (a range; all of them if None) of the stacked
    params and cache.

    Only the layer index is scanned.  The weights are indexed at ``l``
    inside the body, and the whole stacked cache rides the carry, each
    layer reading and writing its own part of it in place: pools
    (:func:`is_pool`) through page tables offset by ``l * num_pages`` into
    the layer-flattened pools, other leaves at index ``l``.  No layer's pool is sliced out of the stack, no
    stack is assembled from the layers' outputs, and a range of layers
    (one side of the C3-SL cut) slices neither weights nor cache, so XLA
    keeps one buffer per leaf across the loop and across the cut."""
    kinds = {f"l{li}_{si}_{kind}": kind
             for li, layer in enumerate(cfg.block_pattern)
             for si, kind in enumerate(layer)}
    pooled = {key: is_pool(kind, paged) for key, kind in kinds.items()}
    if layers is None:
        layers = range(jax.tree.leaves(stacked)[0].shape[0])

    def tables(l):
        if paged is None:
            return pages, pages_swa
        swa = None if pages_swa is None else pages_swa + l * paged.num_pages_swa
        return pages + l * paged.num_pages, swa

    def body(carry, l):
        h, cache = carry
        p_sb = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False),
            stacked)
        view = {key: _layer_view(cache[key], l, pooled[key]) for key in kinds}
        h, new = apply_sb(p_sb, view, h, *tables(l))
        cache = {key: _put_layer(cache[key], new[key], l, pooled[key])
                 for key in kinds}
        return (h, cache), None

    layer_ids = jnp.arange(layers.start, layers.stop, dtype=jnp.int32)
    (h, cache), _ = jax.lax.scan(body, (h, cache), layer_ids)
    return h, cache


def apply_stack_decode(stacked, cache, cfg: ModelConfig, h, pos, *, memory=None,
                       paged=None, pages=None, pages_swa=None, live=None,
                       kv_read="gather", layers=None):
    """One-token decode through superblocks ``layers`` (a range; all if
    None) of the stacked params and cache.  Returns (h, new_cache), the
    cache updated in place (see :func:`_scan_layers`).  Page tables
    (``pages`` / ``pages_swa``) are shared by every superblock."""

    def apply_sb(p_sb, cache_sb, h, pages_l, pages_swa_l):
        return apply_superblock_decode(p_sb, cache_sb, cfg, h, pos,
                                       memory=memory, paged=paged,
                                       pages=pages_l, pages_swa=pages_swa_l,
                                       live=live, kv_read=kv_read)

    return _scan_layers(stacked, cache, cfg, h, layers, apply_sb, paged,
                        pages, pages_swa)


# ---------------------------------------------------------------------------
# chunked prefill (C tokens per row, per-row start positions, ragged tails)
# ---------------------------------------------------------------------------

def _prefill_stateful(kind: str, p, cache, cfg: ModelConfig, x, valid):
    """Recurrent sublayers advance sequentially INSIDE the program: a
    lax.scan over the chunk's C positions reusing the O(1) decode step,
    committing state only where ``valid`` (padded positions leave state and
    token-shift buffers untouched).  One dispatch regardless of C."""

    def step(state, inp):
        x_j, v_j = inp                                   # (B,d), (B,)
        if kind == "mamba":
            y, ns = mamba_lib.apply_mamba_decode(p, x_j[:, None], state,
                                                 d_state=cfg.d_state)
        elif kind == "rwkv_tm":
            st = {"wkv": state["wkv"], "x_prev_tm": state["x_prev"]}
            y, st = rwkv_lib.apply_rwkv_timemix_decode(p, x_j[:, None], st,
                                                       num_heads=cfg.num_heads)
            ns = {"wkv": st["wkv"], "x_prev": st["x_prev_tm"]}
        else:  # rwkv_cm
            st = {"x_prev_cm": state["x_prev"]}
            y, st = rwkv_lib.apply_rwkv_channelmix_decode(p, x_j[:, None], st)
            ns = {"x_prev": st["x_prev_cm"]}
        ns = jax.tree.map(
            lambda n, o: jnp.where(v_j.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            ns, state)
        return ns, y[:, 0]

    new_cache, ys = jax.lax.scan(step, cache, (x.swapaxes(0, 1), valid.T))
    return ys.swapaxes(0, 1), new_cache


def apply_sublayer_prefill(kind: str, p, cache, cfg: ModelConfig, h, pos,
                           valid, *, memory=None, paged=None, pages=None,
                           pages_swa=None):
    """Chunked-prefill sublayer step.  h (B,C,d); pos (B,) start positions;
    valid (B,C) marks real tokens.  Returns (residual update, new_cache).
    Padded positions never touch caches or recurrent state; their outputs
    are garbage the caller must mask/ignore."""
    x = _apply_norm(cfg, p["norm"], h)
    if kind == "attn":
        y, new_cache = attn_lib.apply_gqa_prefill(
            p, x, cache, pos, valid, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
            rotary_dim=cfg.rotary_dim, rope_theta=cfg.rope_theta,
            sliding_window=cfg.sliding_window,
            **_paged_args(kind, cfg, paged, pages, pages_swa))
    elif kind == "mla":
        y, new_cache = attn_lib.apply_mla_prefill(
            p, x, cache, pos, valid, num_heads=cfg.num_heads,
            kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
            qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta,
            **_paged_args(kind, cfg, paged, pages, pages_swa))
    elif kind == "cross":
        y = attn_lib.apply_cross_attention(p, x, memory, num_heads=cfg.num_heads,
                                           num_kv_heads=cfg.num_kv_heads,
                                           head_dim=cfg.head_dim_)
        new_cache = cache
    elif kind == "mlp":
        y, new_cache = apply_mlp(p, x), cache
    elif kind == "moe":
        if "router" in p:
            # full capacity, exactly like decode: serving never drops tokens,
            # which also keeps every position independent of its chunk-mates
            y, _ = moe_lib.apply_moe(p, x, top_k=cfg.experts_per_token,
                                     capacity_factor=float(cfg.num_experts))
        else:
            y = apply_mlp(p, x)
        new_cache = cache
    elif kind in ("mamba", "rwkv_tm", "rwkv_cm"):
        y, new_cache = _prefill_stateful(kind, p, cache, cfg, x, valid)
    else:
        raise ValueError(kind)
    return y, new_cache


def apply_superblock_prefill(p_sb, cache_sb, cfg: ModelConfig, h, pos, valid, *,
                             pattern=None, memory=None, paged=None, pages=None,
                             pages_swa=None):
    pattern = pattern or cfg.block_pattern
    new_cache = {}
    for li, layer in enumerate(pattern):
        for si, kind in enumerate(layer):
            key = f"l{li}_{si}_{kind}"
            y, new_cache[key] = apply_sublayer_prefill(
                kind, p_sb[key], cache_sb[key], cfg, h, pos, valid,
                memory=memory, paged=paged, pages=pages, pages_swa=pages_swa)
            h = h + y
    return h, new_cache


def apply_stack_prefill(stacked, cache, cfg: ModelConfig, h, pos, valid, *,
                        memory=None, paged=None, pages=None, pages_swa=None,
                        layers=None):
    """Chunked prefill through superblocks ``layers`` (a range; all if None)
    of the stacked params and cache.  Returns (h (B,C,d), new_cache), the
    cache updated in place as in :func:`apply_stack_decode`."""

    def apply_sb(p_sb, cache_sb, h, pages_l, pages_swa_l):
        return apply_superblock_prefill(p_sb, cache_sb, cfg, h, pos, valid,
                                        memory=memory, paged=paged,
                                        pages=pages_l, pages_swa=pages_swa_l)

    return _scan_layers(stacked, cache, cfg, h, layers, apply_sb, paged,
                        pages, pages_swa)
