"""Pallas paged-attention decode kernel: page-table walk INSIDE the kernel.

The gather path (``repro.models.paging.gather_pages``) re-materializes a
contiguous ``(B, T, KV, hd)`` view of the page pools on every decode step —
one full pool read plus a same-size write and re-read per cache leaf, the
materialization tax ROADMAP names as the biggest raw-speed lever left in
the repo.  This kernel walks the per-slot page table with
``PrefetchScalarGridSpec`` instead: grid ``(B, P)``, and the block index
map of each pool operand is ``table[b, p]`` — the pages stream
HBM -> VMEM directly in page-table order, and the contiguous view never
exists (vLLM's PagedAttention, expressed in Pallas).

Bit-identical equivalence with the gather path (under interpret mode) is
the design constraint — the serving suite pins greedy outputs, not
tolerances — so the reduction is NOT a flash-style online softmax: once
a slot's pages sit in a head-major VMEM strip, the kernel calls
``repro.models.attention.attend_slot`` / ``attend_slot_quant``, the same
per-slot function the gather read vmaps over slots.  Decode-step VMEM
holds the whole per-slot K/V strip up to T=2048 at KV=32 in f32 (see
kernels/README.md for the budget), so tiling the T axis would cost the
bitwise guarantee for nothing at the serving engine's shapes.

Coverage: GQA/MHA decode (linear caches and ring-buffer SWA) with float
or int8-quantized KV pools.  MLA latent caches and prefill stay on the
gather path — the serving engine falls back LOUDLY (see
``BatchedEngine(kv_read=...)``), never silently.

Like the circconv kernels, this runs in interpret mode off-TPU
(``circconv._interpret``); callers surface the effective execution mode
instead of pretending interpret numbers are kernel numbers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import circconv
from repro.models import attention as attn_lib

def _land(acc, page, p, ps):
    """Append one (ps, KV, hd) page to the slot's head-major (KV, P*ps, hd)
    strip, so the compute step runs leading-batch dots over heads."""
    acc[:, pl.ds(p * ps, ps), :] = jnp.swapaxes(page, 0, 1)


def _attn_kernel(table_ref, pos_ref, q_ref, k_pool_ref, v_pool_ref, out_ref,
                 k_acc, v_acc, *, T: int, ps: int, P: int, sliding_window):
    """Float-KV body.  Grid (B, P): step (b, p) lands page table[b, p] in
    VMEM via the block index map and appends it to the slot's scratch
    strip; the last page step runs ``attend_slot`` over the strip."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    _land(k_acc, k_pool_ref[0], p, ps)
    _land(v_acc, v_pool_ref[0], p, ps)

    @pl.when(p == P - 1)
    def _compute():
        valid = attn_lib.decode_valid(pos_ref[b], T, sliding_window)
        out_ref[0] = attn_lib.attend_slot(q_ref[0], k_acc[:, :T],
                                          v_acc[:, :T], valid)


def _attn_kernel_quant(table_ref, pos_ref, q_ref, k_pool_ref, ks_pool_ref,
                       v_pool_ref, vs_pool_ref, out_ref, k_acc, ks_acc,
                       v_acc, vs_acc, *, T: int, ps: int, P: int,
                       sliding_window, compute_dtype):
    """int8-KV body: pages stream as int8 + per-(pos, kv-head) scales and
    the last page step runs ``attend_slot_quant`` (the dequantized cache
    is never materialized).  Scales land lane-dense as (P, ps, KV): a
    (P*ps, KV, 1) strip would pad its last dim to 128 lanes."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    _land(k_acc, k_pool_ref[0], p, ps)
    _land(v_acc, v_pool_ref[0], p, ps)
    ks_acc[p] = ks_pool_ref[0, :, :, 0]
    vs_acc[p] = vs_pool_ref[0, :, :, 0]

    @pl.when(p == P - 1)
    def _compute():
        KV = ks_acc.shape[-1]

        def head_major(acc):                 # (P, ps, KV) -> (KV, T)
            return acc[...].reshape(P * ps, KV)[:T].T

        valid = attn_lib.decode_valid(pos_ref[b], T, sliding_window)
        out_ref[0] = attn_lib.attend_slot_quant(
            q_ref[0], k_acc[:, :T], head_major(ks_acc), v_acc[:, :T],
            head_major(vs_acc), valid, compute_dtype)


def _check_geometry(q, pool, table, length):
    B, Sq, H, hd = q.shape
    if Sq != 1:
        raise ValueError(f"decode kernel takes one query token, got Sq={Sq}")
    P = table.shape[1]
    ps, KV = pool.shape[1], pool.shape[2]
    if table.shape[0] != B:
        raise ValueError(f"page table batch {table.shape[0]} != query batch {B}")
    if length > P * ps:
        raise ValueError(f"length {length} exceeds table capacity {P}x{ps}")
    if H % KV:
        raise ValueError(f"H={H} not a multiple of KV={KV}")
    return B, H, hd, P, ps, KV


def _padded_bytes(shape, dtype):
    """VMEM bytes of a block: the last two dims pad to the (sublane, 128)
    tile, sublanes to 32 bytes' worth of rows (8 f32, 16 bf16, 32 int8)."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sub = 32 // item
    n = -(-rows // sub) * sub * (-(-lanes // 128) * 128) * item
    for d in lead:
        n *= d
    return n


# v5e and later hold 128 MiB of VMEM per core; the default scoped limit
# (16 MiB on v5e) is below one slot's K+V strip at deepseek-7b widths.
_VMEM_CAP = 100 * 2 ** 20


def _compiler_params(blocks, scratch, temps):
    """Double-buffered blocks + scratch + the compute step's f32 values,
    with 25% headroom for Mosaic's own stack."""
    need = (2 * sum(_padded_bytes(*b) for b in blocks)
            + sum(_padded_bytes(*s) for s in scratch)
            + sum(_padded_bytes(*t) for t in temps))
    limit = min(max(need + need // 4, 16 * 2 ** 20), _VMEM_CAP)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=limit)


def paged_attention(q, k_pool, v_pool, table, pos, *, length: int,
                    sliding_window=None):
    """q (B, 1, H, hd) post-rope; k/v pools (num_pages, ps, KV, hd); table
    (B, P) int32; pos (B,) int32.  Returns the (B, 1, H*hd) attention
    output — ``attention.sdpa_decode`` over ``gather_pages`` of the same
    pools, computed in-kernel."""
    B, H, hd, P, ps, KV = _check_geometry(q, k_pool, table, length)
    G = H // KV
    page = (ps, KV, hd)
    qspec = pl.BlockSpec((1, KV, G, hd), lambda b, p, tab, pos: (b, 0, 0, 0))
    pspec = pl.BlockSpec((1,) + page, lambda b, p, tab, pos: (tab[b, p], 0, 0, 0))
    strip = (KV, P * ps, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[qspec, pspec, pspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM(strip, k_pool.dtype),
                        pltpu.VMEM(strip, v_pool.dtype)],
    )
    kernel = functools.partial(_attn_kernel, T=length, ps=ps, P=P,
                               sliding_window=sliding_window)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=_compiler_params(
            blocks=[((KV, G, hd), q.dtype)] * 2 + [(page, k_pool.dtype)] * 2,
            scratch=[(strip, k_pool.dtype)] * 2,
            temps=[((KV, length, hd), jnp.float32)] * 2),
        interpret=circconv._interpret(),
    )(table.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(B, KV, G, hd), k_pool, v_pool)
    return out.reshape(B, 1, H * hd)


def paged_attention_quant(q, k_pool, k_scale_pool, v_pool, v_scale_pool,
                          table, pos, *, length: int, sliding_window=None,
                          compute_dtype=None):
    """int8-KV variant: scale pools (num_pages, ps, KV, 1) ride the same
    page table.  The int8 ``attention.sdpa_decode``, computed in-kernel."""
    B, H, hd, P, ps, KV = _check_geometry(q, k_pool, table, length)
    G = H // KV
    compute_dtype = compute_dtype or q.dtype
    page, spage = (ps, KV, hd), (ps, KV, 1)
    qspec = pl.BlockSpec((1, KV, G, hd), lambda b, p, tab, pos: (b, 0, 0, 0))
    pspec = pl.BlockSpec((1,) + page, lambda b, p, tab, pos: (tab[b, p], 0, 0, 0))
    sspec = pl.BlockSpec((1,) + spage, lambda b, p, tab, pos: (tab[b, p], 0, 0, 0))
    strip, sstrip = (KV, P * ps, hd), (P, ps, KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[qspec, pspec, sspec, pspec, sspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM(strip, k_pool.dtype),
                        pltpu.VMEM(sstrip, k_scale_pool.dtype),
                        pltpu.VMEM(strip, v_pool.dtype),
                        pltpu.VMEM(sstrip, v_scale_pool.dtype)],
    )
    kernel = functools.partial(_attn_kernel_quant, T=length, ps=ps, P=P,
                               sliding_window=sliding_window,
                               compute_dtype=compute_dtype)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), compute_dtype),
        compiler_params=_compiler_params(
            blocks=[((KV, G, hd), q.dtype)] * 2 + [(page, k_pool.dtype)] * 2
            + [(spage, k_scale_pool.dtype)] * 2,
            scratch=[(strip, k_pool.dtype)] * 2
            + [(sstrip, k_scale_pool.dtype)] * 2,
            temps=[((KV, length, hd), jnp.float32)] * 2),
        interpret=circconv._interpret(),
    )(table.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(B, KV, G, hd), k_pool, k_scale_pool, v_pool, v_scale_pool)
    return out.reshape(B, 1, H * hd)
