"""Attention variants: GQA (+bias, sliding window), cross-attention, MLA.

All functions are pure; params are plain dicts.  Shapes:
    x (B, S, D); q heads H, kv heads KV, head dim hd.
Decode functions take a KV cache and one new token (B, 1, D) at position
`pos` (scalar int32), returning (y, new_cache).  Sliding-window caches are
ring buffers of length `window`.

Every decode/prefill function supports two cache layouts:

* contiguous (default) — cache leaves are per-slot strips (B, T, ...).
* paged — cache leaves are shared pools (num_pages, page_size, ...) and
  ``pages`` carries the per-slot page table (B, P); ``length`` gives the
  logical per-slot cache length T the contiguous layout would have.
  Reads gather the pool into the exact contiguous (B, T, ...) view
  (repro.models.paging.gather_pages) so masks and SDPA are the same code
  on both layouts — that is what keeps paged outputs bit-identical.
  GQA decode additionally takes ``kv_read="kernel"``: the Pallas
  paged-attention kernel walks the page table in-kernel (no contiguous
  gather) while reproducing the gather path's values bit-for-bit.

Decode functions also take ``live`` (B,) bool: rows marked False write
NOTHING to the cache (the serving engine decodes while other slots are
mid-prefill or empty; unmasked writes would stomp their pages).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import paging
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(rng, d_model: int, num_heads: int, num_kv_heads: int, head_dim: int,
             qkv_bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p = {
        "w_q": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "w_k": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "w_v": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "w_o": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["b_q"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["b_k"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
        p["b_v"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
    return p


def _qkv(p, x, num_heads, num_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ p["w_q"] + p.get("b_q", 0.0)
    k = x @ p["w_k"] + p.get("b_k", 0.0)
    v = x @ p["w_v"] + p.get("b_v", 0.0)
    return (q.reshape(B, S, num_heads, head_dim),
            k.reshape(B, S, num_kv_heads, head_dim),
            v.reshape(B, S, num_kv_heads, head_dim))


def _sdpa(q, k, v, mask):
    """q (B,Sq,H,hd), k (B,Sk,KV,hd), v (B,Sk,KV,hd_v) — hd_v may differ
    (MLA).  mask broadcastable (B,1,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    hd_v = v.shape[-1]
    groups = H // KV
    qg = q.reshape(B, Sq, KV, groups, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    scores = jnp.where(mask[:, :, None, :, :] if mask.ndim == 4 else mask,
                       scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H * hd_v)


def causal_mask(Sq: int, Sk: int, window: int | None = None,
                q0: int = 0, k0: int = 0):
    """(1, 1, Sq, Sk) boolean for a (q, k) tile at absolute offsets (q0, k0)."""
    qpos = q0 + jnp.arange(Sq)[:, None]
    kpos = k0 + jnp.arange(Sk)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None]


# Above this sequence length, attention runs q-chunked with per-chunk remat
# so the live score tensor is (B, H, q_chunk, kv_len) instead of (B, H, S, S).
# (The TPU production path would be a Pallas flash kernel; this is the
# HLO-level equivalent that bounds memory identically.)
CHUNK_THRESHOLD = 2048
Q_CHUNK = 1024


def _sdpa_causal(q, k, v, window: int | None = None, q_chunk: int = Q_CHUNK):
    """Causal SDPA, q-chunked above CHUNK_THRESHOLD.  Static chunk bounds:
    chunk i attends kv[max(0, i*qc - window + 1) : (i+1)*qc)."""
    S = q.shape[1]
    if S <= CHUNK_THRESHOLD:
        return _sdpa(q, k, v, causal_mask(S, S, window))
    qc = min(q_chunk, S)
    while S % qc:
        qc -= 1

    def one_chunk(q_i, k_i, v_i, mask):
        return _sdpa(q_i, k_i, v_i, mask)

    one_chunk = jax.checkpoint(one_chunk)
    outs = []
    for i in range(S // qc):
        q0 = i * qc
        kv_end = q0 + qc
        kv_start = 0 if window is None else max(0, q0 - window + 1)
        # align start down to the chunk grid (keeps slice sizes uniform-ish)
        kv_start -= kv_start % qc
        mask = causal_mask(qc, kv_end - kv_start, window, q0=q0, k0=kv_start)
        outs.append(one_chunk(q[:, q0:kv_end], k[:, kv_start:kv_end],
                              v[:, kv_start:kv_end], mask))
    return jnp.concatenate(outs, axis=1)


def apply_gqa(p, x, positions, *, num_heads, num_kv_heads, head_dim,
              rotary_dim, rope_theta=10000.0, sliding_window=None):
    B, S, D = x.shape
    q, k, v = _qkv(p, x, num_heads, num_kv_heads, head_dim)
    q = apply_rope(q, positions, rotary_dim, rope_theta)
    k = apply_rope(k, positions, rotary_dim, rope_theta)
    return _sdpa_causal(q, k, v, sliding_window) @ p["w_o"]


def apply_cross_attention(p, x, memory, *, num_heads, num_kv_heads, head_dim):
    """x (B,Sq,D) attends to memory (B,Sk,D); no mask, no rope."""
    B, Sq, _ = x.shape
    Sk = memory.shape[1]
    q = (x @ p["w_q"] + p.get("b_q", 0.0)).reshape(B, Sq, num_heads, head_dim)
    k = (memory @ p["w_k"] + p.get("b_k", 0.0)).reshape(B, Sk, num_kv_heads, head_dim)
    v = (memory @ p["w_v"] + p.get("b_v", 0.0)).reshape(B, Sk, num_kv_heads, head_dim)
    mask = jnp.ones((1, 1, Sq, Sk), bool)
    return _sdpa(q, k, v, mask) @ p["w_o"]


def init_gqa_cache(batch: int, length: int, num_kv_heads: int, head_dim: int,
                   dtype=jnp.float32, quant: bool = False):
    """KV cache.  quant=True stores int8 values + per-(pos, kv-head) scales
    (2x less HBM than bf16; scales are folded into scores/probs at use so
    the dequantized cache is never materialized)."""
    shape = (batch, length, num_kv_heads, head_dim)
    if quant:
        sshape = (batch, length, num_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x):
    """x (B,1,KV,hd) -> (int8 values, (B,1,KV,1) scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decode_valid(pos, T: int, sliding_window=None):
    """(1, T) validity of one slot's decode read at position ``pos``: a
    linear cache sees positions <= pos; a ring buffer of length T sees its
    last min(pos+1, T) writes.  A 2-D iota, so Mosaic lowers it too."""
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    if sliding_window is not None:
        age = (pos % T - idx) % T
        return age < jnp.minimum(pos + 1, T)
    return idx <= pos


def attend_slot(q, k, v, valid):
    """One slot's decode attention, head-major: q (KV, G, hd), k/v
    (KV, T, hd), valid (1, T) -> (KV, G, hd).  The gather read vmaps it
    over slots and the Pallas kernel runs it per slot, so both read paths
    compute the same ops in the same order."""
    hd = q.shape[-1]
    scores = jnp.einsum("kgh,ksh->kgs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (hd ** -0.5)
    scores = jnp.where(valid[None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("kgs,ksh->kgh", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def attend_slot_quant(q, k_q, k_scale, v_q, v_scale, valid, compute_dtype):
    """:func:`attend_slot` over an int8 cache, scales (KV, T): they fold
    into scores/probs, so only the int8 tensors stream from HBM."""
    hd = q.shape[-1]
    scores = jnp.einsum("kgh,ksh->kgs", q.astype(jnp.float32),
                        k_q.astype(jnp.float32))
    scores = scores * k_scale[:, None, :]
    scores = scores * (hd ** -0.5)
    scores = jnp.where(valid[None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs * v_scale[:, None, :]
    out = jnp.einsum("kgs,ksh->kgh", probs, v_q.astype(jnp.float32))
    return out.astype(compute_dtype)


def sdpa_decode(q, view, pos, T: int, sliding_window=None,
                compute_dtype=None):
    """The gather read's decode attention: q (B, 1, H, hd) over the
    (B, T, KV, ...) cache ``view``, slot b at position pos[b].  Returns
    (B, 1, H*hd)."""
    B, _, H, hd = q.shape
    KV = view["k"].shape[2]
    qh = q.reshape(B, KV, H // KV, hd)
    valid = jax.vmap(lambda p: decode_valid(p, T, sliding_window))(pos)

    def head_major(x):                 # (B, T, KV, ...) -> (B, KV, T, ...)
        return jnp.swapaxes(x, 1, 2)

    if "k_scale" in view:
        attend = functools.partial(attend_slot_quant,
                                   compute_dtype=compute_dtype or q.dtype)
        out = jax.vmap(attend)(qh, head_major(view["k"]),
                               head_major(view["k_scale"][..., 0]),
                               head_major(view["v"]),
                               head_major(view["v_scale"][..., 0]), valid)
    else:
        out = jax.vmap(attend_slot)(qh, head_major(view["k"]),
                                    head_major(view["v"]), valid)
    return out.reshape(B, 1, H * hd)


def _per_row_update(cache_kv, new_kv, slots):
    """Write new_kv (B,1,KV,hd) into cache (B,T,KV,hd) at per-row slots (B,)."""
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0)
    )(cache_kv, new_kv, slots)


def _write_rows(cache, new, slots, T, *, pages, live):
    """Decode-step cache write (one position per row) on either layout.

    ``new`` maps leaf name -> (B, 1, ...) values.  Paged: scatter through
    the page table.  Contiguous with ``live``: rows not live scatter to
    slot T -> dropped.  Contiguous without ``live``: the original
    dynamic-update path (bit-for-bit the legacy baseline)."""
    if pages is not None:
        return {n: paging.scatter_rows(cache[n], pages, slots, val, live=live)
                for n, val in new.items()}
    if live is not None:
        b_idx = jnp.arange(slots.shape[0])
        wslot = jnp.where(live, slots, T)
        return {n: cache[n].at[b_idx, wslot].set(val[:, 0], mode="drop")
                for n, val in new.items()}
    return {n: _per_row_update(cache[n], val, slots) for n, val in new.items()}


def _write_chunk(cache, new, slots, valid, T, *, pages):
    """Prefill-chunk cache write: ``new`` maps leaf name -> (B, C, ...)
    values at logical slots (B, C); ``valid`` False (padded tails, rows not
    prefilling) drops the write on both layouts."""
    if pages is not None:
        return {n: paging.scatter_chunk(cache[n], pages, slots, valid, val)
                for n, val in new.items()}
    idx = jnp.where(valid, slots, T)
    b_idx = jnp.arange(slots.shape[0])[:, None]
    return {n: cache[n].at[b_idx, idx].set(val, mode="drop")
            for n, val in new.items()}


def _view(cache, pages, T):
    """The (B, T, ...) per-slot view attention reads: the cache itself on
    the contiguous layout, a gather of the pools on the paged one."""
    if pages is None:
        return cache
    return {n: paging.gather_pages(cache[n], pages, T) for n in cache}


def apply_gqa_decode(p, x, cache, pos, *, num_heads, num_kv_heads, head_dim,
                     rotary_dim, rope_theta=10000.0, sliding_window=None,
                     pages=None, length=None, live=None, kv_read="gather"):
    """One-token decode. x (B,1,D); cache k/v (B,T,KV,hd) (T=window for SWA),
    or pooled (num_pages, ps, KV, hd) when ``pages`` is given.

    pos may be a scalar (lockstep batch) or (B,) int32 (continuous batching:
    every slot at its own position).  ``live`` (B,) masks cache writes (a
    non-live row attends garbage the caller must ignore but writes nothing).
    Returns (y (B,1,D), new_cache).

    ``kv_read`` selects how a PAGED cache is read: ``"gather"``
    materializes the contiguous view (paging.gather_pages) and reads it
    with the contiguous layout's ``sdpa_decode``; ``"kernel"`` walks the page table inside the Pallas
    paged-attention kernel (repro.kernels.paged_attention) — no contiguous
    gather, bit-identical outputs by construction (the kernel runs
    attend_slot / attend_slot_quant per slot on the same values).
    """
    B = x.shape[0]
    paged = pages is not None
    if kv_read not in ("gather", "kernel"):
        raise ValueError(f"unknown kv_read {kv_read!r} "
                         "(expected 'gather' | 'kernel')")
    if kv_read == "kernel" and not paged:
        raise ValueError("kv_read='kernel' requires the paged cache layout "
                         "(the kernel is a page-table walk; contiguous "
                         "caches have no table to walk)")
    T = length if paged else cache["k"].shape[1]
    q, k, v = _qkv(p, x, num_heads, num_kv_heads, head_dim)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos_b[:, None]
    q = apply_rope(q, positions, rotary_dim, rope_theta)
    k = apply_rope(k, positions, rotary_dim, rope_theta)
    slots = pos_b % T if sliding_window is not None else pos_b
    quant = "k_scale" in cache
    if quant:
        k_q, k_s = _quantize_kv(k)
        v_q, v_s = _quantize_kv(v)
        new = {"k": k_q, "v": v_q, "k_scale": k_s, "v_scale": v_s}
    else:
        new = {"k": k, "v": v}
    new_cache = _write_rows(cache, new, slots, T, pages=pages, live=live)
    if kv_read == "kernel":
        # in-kernel page-table walk: reads the SAME post-write pools the
        # gather path would view, applies the same mask math in-kernel
        from repro.kernels import ops as kops
        att = kops.paged_attention_decode(q, new_cache, pages, pos_b,
                                          length=T,
                                          sliding_window=sliding_window,
                                          compute_dtype=x.dtype)
        return att @ p["w_o"], new_cache
    view = _view(new_cache, pages, T)
    y = sdpa_decode(q, view, pos_b, T, sliding_window, x.dtype) @ p["w_o"]
    return y, new_cache


def apply_gqa_prefill(p, x, cache, pos, valid, *, num_heads, num_kv_heads,
                      head_dim, rotary_dim, rope_theta=10000.0,
                      sliding_window=None, pages=None, length=None):
    """Chunked prefill: ingest C tokens per row in ONE dispatch.

    x (B,C,D); cache k/v (B,T,KV,hd) (T=window for SWA) or pooled with page
    table ``pages``; pos (B,) per-row start positions; valid (B,C) marks
    real tokens (False = ragged-tail padding or rows not prefilling: no
    cache write, no attention contribution).  Returns (y (B,C,D), new_cache).

    Attention runs over [pre-chunk cache ; chunk keys] — never the
    post-write cache — so ring buffers stay correct: a chunk write that
    reuses a ring slot cannot shadow the old occupant some earlier query
    should still see.  For SWA the chunk size must be <= T (each ring slot
    written at most once per chunk).
    """
    B, C, D = x.shape
    paged = pages is not None
    T = length if paged else cache["k"].shape[1]
    if sliding_window is not None and C > T:
        raise ValueError(f"chunk size {C} exceeds ring-buffer length {T}")
    q, k, v = _qkv(p, x, num_heads, num_kv_heads, head_dim)
    pos = jnp.asarray(pos, jnp.int32)
    qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)         # (B,C) absolute
    q = apply_rope(q, qpos, rotary_dim, rope_theta)
    k = apply_rope(k, qpos, rotary_dim, rope_theta)

    # pre-chunk cache validity: slot s last held absolute position
    # last_s = (pos-1) - ((pos-1-s) mod T)  (< 0 => never written).  For a
    # linear cache (T >= max_len) this reduces to last_s = s iff s < pos.
    s_idx = jnp.arange(T, dtype=jnp.int32)
    last = (pos[:, None] - 1) - ((pos[:, None] - 1 - s_idx) % T)  # (B,T)
    m_cache = jnp.broadcast_to((last >= 0)[:, None, :], (B, C, T))
    m_chunk = (qpos[:, :, None] >= qpos[:, None, :]) & valid[:, None, :]
    if sliding_window is not None:
        m_cache = m_cache & (last[:, None, :] > qpos[:, :, None] - sliding_window)
        m_chunk = m_chunk & (qpos[:, None, :] > qpos[:, :, None] - sliding_window)
    mask = jnp.concatenate([m_cache, m_chunk], axis=-1)[:, None]  # (B,1,C,T+C)

    cview = _view(cache, pages, T)
    quant = "k_scale" in cache
    if quant:
        # dequantized *view* for the prefill matmuls (transient, prefill-only;
        # the decode hot loop keeps streaming int8 via attend_slot_quant)
        ck = (cview["k"].astype(jnp.float32) * cview["k_scale"]).astype(x.dtype)
        cv = (cview["v"].astype(jnp.float32) * cview["v_scale"]).astype(x.dtype)
    else:
        ck, cv = cview["k"], cview["v"]
    y = _sdpa(q, jnp.concatenate([ck, k], axis=1),
              jnp.concatenate([cv, v], axis=1), mask) @ p["w_o"]

    # write the chunk; padded tokens scatter to index T == out of bounds -> drop
    slot = qpos % T if sliding_window is not None else qpos
    if quant:
        k_q, k_s = _quantize_kv(k)
        v_q, v_s = _quantize_kv(v)
        new = {"k": k_q, "v": v_q, "k_scale": k_s, "v_scale": v_s}
    else:
        new = {"k": k, "v": v}
    return y, _write_chunk(cache, new, slot, valid, T, pages=pages)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(rng, d_model: int, num_heads: int, *, kv_lora_rank: int,
             qk_nope_dim: int, qk_rope_dim: int, v_head_dim: int, dtype=jnp.float32):
    ks = jax.random.split(rng, 6)
    H = num_heads
    return {
        "w_q": dense_init(ks[0], d_model, H * (qk_nope_dim + qk_rope_dim), dtype),
        "w_dkv": dense_init(ks[1], d_model, kv_lora_rank, dtype),
        "kv_norm": jnp.ones((kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[2], kv_lora_rank, H * qk_nope_dim, dtype),
        "w_uv": dense_init(ks[3], kv_lora_rank, H * v_head_dim, dtype),
        "w_kpe": dense_init(ks[4], d_model, qk_rope_dim, dtype),
        "w_o": dense_init(ks[5], H * v_head_dim, d_model, dtype),
    }


def _mla_qc(p, x, positions, *, num_heads, qk_nope_dim, qk_rope_dim, rope_theta):
    from repro.models.layers import rms_norm
    B, S, _ = x.shape
    H = num_heads
    q = (x @ p["w_q"]).reshape(B, S, H, qk_nope_dim + qk_rope_dim)
    q_nope, q_rope = q[..., :qk_nope_dim], q[..., qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, qk_rope_dim, rope_theta)
    c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"])                  # (B,S,L)
    k_pe = apply_rope((x @ p["w_kpe"])[:, :, None, :], positions,
                      qk_rope_dim, rope_theta)[:, :, 0, :]          # (B,S,rope)
    return q_nope, q_rope, c_kv, k_pe


def apply_mla(p, x, positions, *, num_heads, kv_lora_rank, qk_nope_dim,
              qk_rope_dim, v_head_dim, rope_theta=10000.0, sliding_window=None):
    B, S, _ = x.shape
    H = num_heads
    q_nope, q_rope, c_kv, k_pe = _mla_qc(
        p, x, positions, num_heads=H, qk_nope_dim=qk_nope_dim,
        qk_rope_dim=qk_rope_dim, rope_theta=rope_theta)
    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, qk_nope_dim)
    v = (c_kv @ p["w_uv"]).reshape(B, S, H, v_head_dim)
    # concat the rope component (k_pe shared across heads) so the fused
    # q_cat . k_cat score equals the MLA score; reuses the chunked SDPA.
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_cat = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, qk_rope_dim))],
        axis=-1)
    return _sdpa_causal(q_cat, k_cat, v, sliding_window) @ p["w_o"]


def init_mla_cache(batch: int, length: int, kv_lora_rank: int, qk_rope_dim: int,
                   dtype=jnp.float32):
    """MLA's win: the cache stores the COMPRESSED c_kv + shared k_pe."""
    return {"c_kv": jnp.zeros((batch, length, kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, length, qk_rope_dim), dtype)}


def apply_mla_decode(p, x, cache, pos, *, num_heads, kv_lora_rank, qk_nope_dim,
                     qk_rope_dim, v_head_dim, rope_theta=10000.0,
                     pages=None, length=None, live=None):
    """Absorbed-matrices MLA decode: scores live in the kv_lora space.
    pos: scalar or (B,) int32 (continuous batching); ``pages``/``length``
    select the paged cache layout, ``live`` masks cache writes."""
    B = x.shape[0]
    H = num_heads
    paged = pages is not None
    T = length if paged else cache["c_kv"].shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q_nope, q_rope, c_kv_new, k_pe_new = _mla_qc(
        p, x, pos_b[:, None], num_heads=H,
        qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim, rope_theta=rope_theta)
    new = {"c_kv": c_kv_new, "k_pe": k_pe_new}
    new_cache = _write_rows(cache, new, pos_b, T, pages=pages, live=live)
    view = _view(new_cache, pages, T)
    c_kv = view["c_kv"]
    k_pe = view["k_pe"]
    # absorb W_uk into q: q_eff (B,H,L)
    w_uk = p["w_uk"].reshape(kv_lora_rank, H, qk_nope_dim)
    q_eff = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], w_uk)
    scale = (qk_nope_dim + qk_rope_dim) ** -0.5
    scores = (jnp.einsum("bhl,btl->bht", q_eff, c_kv)
              + jnp.einsum("bhd,btd->bht", q_rope[:, 0], k_pe)).astype(jnp.float32)
    scores = scores * scale
    valid = jnp.arange(T)[None, None, :] <= pos_b[:, None, None]
    probs = jax.nn.softmax(jnp.where(valid, scores, NEG_INF), axis=-1).astype(x.dtype)
    o_c = jnp.einsum("bht,btl->bhl", probs, c_kv)                  # (B,H,L)
    w_uv = p["w_uv"].reshape(kv_lora_rank, H, v_head_dim)
    out = jnp.einsum("bhl,lhv->bhv", o_c, w_uv).reshape(B, 1, H * v_head_dim)
    return out @ p["w_o"], new_cache


def apply_mla_prefill(p, x, cache, pos, valid, *, num_heads, kv_lora_rank,
                      qk_nope_dim, qk_rope_dim, v_head_dim, rope_theta=10000.0,
                      pages=None, length=None):
    """Chunked absorbed-matrices MLA prefill: C tokens per row, one dispatch.

    x (B,C,D); cache c_kv (B,T,L) / k_pe (B,T,rope), or pooled with page
    table ``pages``; pos (B,) start positions; valid (B,C) as in
    apply_gqa_prefill.  Scores live in the kv_lora space over
    [pre-chunk cache ; chunk latents].
    """
    B, C, _ = x.shape
    H = num_heads
    paged = pages is not None
    T = length if paged else cache["c_kv"].shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)          # (B,C)
    q_nope, q_rope, c_kv_new, k_pe_new = _mla_qc(
        p, x, qpos, num_heads=H, qk_nope_dim=qk_nope_dim,
        qk_rope_dim=qk_rope_dim, rope_theta=rope_theta)
    cview = _view(cache, pages, T)
    c_all = jnp.concatenate([cview["c_kv"], c_kv_new], axis=1)    # (B,T+C,L)
    pe_all = jnp.concatenate([cview["k_pe"], k_pe_new], axis=1)
    w_uk = p["w_uk"].reshape(kv_lora_rank, H, qk_nope_dim)
    q_eff = jnp.einsum("bchd,lhd->bchl", q_nope, w_uk)
    scale = (qk_nope_dim + qk_rope_dim) ** -0.5
    scores = (jnp.einsum("bchl,btl->bhct", q_eff, c_all)
              + jnp.einsum("bchd,btd->bhct", q_rope, pe_all)).astype(jnp.float32)
    scores = scores * scale
    t_idx = jnp.arange(T, dtype=jnp.int32)
    m_cache = jnp.broadcast_to((t_idx[None, :] < pos[:, None])[:, None, :],
                               (B, C, T))
    m_chunk = (qpos[:, :, None] >= qpos[:, None, :]) & valid[:, None, :]
    mask = jnp.concatenate([m_cache, m_chunk], axis=-1)[:, None]  # (B,1,C,T+C)
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1).astype(x.dtype)
    o_c = jnp.einsum("bhct,btl->bchl", probs, c_all)
    w_uv = p["w_uv"].reshape(kv_lora_rank, H, v_head_dim)
    out = jnp.einsum("bchl,lhv->bchv", o_c, w_uv).reshape(B, C, H * v_head_dim)
    new = {"c_kv": c_kv_new, "k_pe": k_pe_new}
    return out @ p["w_o"], _write_chunk(cache, new, qpos, valid, T, pages=pages)
