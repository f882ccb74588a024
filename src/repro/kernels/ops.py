"""Jit'd public wrappers over the Pallas HRR kernels.

Adds custom VJPs over the kernels (which precompute the keys' Toeplitz
blocks themselves).  The codec is linear in Z, and its adjoints are again HRR ops with the SAME keys:

    d/dZ of bind_superpose  == unbind        (correlate the upstream grad)
    d/dS of unbind          == bind_superpose (bind+superpose the upstream grad)

which is exactly how C3-SL compresses the backward-path gradients with zero
extra machinery.  Keys are constants: the VJPs return no key cotangent.
"""
from __future__ import annotations

import jax

from repro.kernels import circconv


@jax.custom_vjp
def bind_superpose_pallas(Z: jax.Array, K: jax.Array) -> jax.Array:
    """Z (G, R, D), K (R, D) -> S (G, D) via the Pallas Toeplitz kernel."""
    return circconv.bind_superpose_kernel(Z, K)


def _bind_fwd(Z, K):
    return bind_superpose_pallas(Z, K), K


def _bind_bwd(K, dS):
    dZ = circconv.unbind_kernel(dS, K)
    return dZ, None


bind_superpose_pallas.defvjp(_bind_fwd, _bind_bwd)


@jax.custom_vjp
def unbind_pallas(S: jax.Array, K: jax.Array) -> jax.Array:
    """S (G, D), K (R, D) -> Zhat (G, R, D) via the Pallas Toeplitz kernel."""
    return circconv.unbind_kernel(S, K)


def _unbind_fwd(S, K):
    return unbind_pallas(S, K), K


def _unbind_bwd(K, dZhat):
    dS = circconv.bind_superpose_kernel(dZhat, K)
    return dS, None


unbind_pallas.defvjp(_unbind_fwd, _unbind_bwd)


# ---------------------------------------------------------------------------
# Paged-attention decode (repro.kernels.paged_attention)
# ---------------------------------------------------------------------------

def paged_attention_decode(q, cache, table, pos, *, length: int,
                           sliding_window=None, compute_dtype=None):
    """Decode-step attention over paged KV pools, page-table walk in-kernel.

    ``q`` (B, 1, H, hd) post-rope; ``cache`` the attn sublayer's pool dict
    ({"k", "v"} float pools, plus {"k_scale", "v_scale"} when int8-
    quantized); ``table`` (B, P) int32 page table; ``pos`` (B,) int32
    per-slot positions.  Returns (B, 1, H*hd), bit-identical to
    ``attention.sdpa_decode`` over ``gather_pages`` of the same pools.

    Inference-only (no custom VJP): decode never differentiates through
    the cache read.  Quantized vs float dispatch mirrors
    ``apply_gqa_decode``'s ``"k_scale" in cache`` seam.
    """
    from repro.kernels import paged_attention as pa
    if "k_scale" in cache:
        return pa.paged_attention_quant(
            q, cache["k"], cache["k_scale"], cache["v"], cache["v_scale"],
            table, pos, length=length, sliding_window=sliding_window,
            compute_dtype=compute_dtype)
    return pa.paged_attention(q, cache["k"], cache["v"], table, pos,
                              length=length, sliding_window=sliding_window)
