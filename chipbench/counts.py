"""Operations and bytes the work needs, worked out from shapes.

These are the least a correct implementation must do, so a share of a
roofline built on them cannot pass 100% unless the time leaves part of
the work out.  FLOPs count a multiply-add as two.
"""
from __future__ import annotations

import math


def layer_matmul_params(m: dict) -> int:
    """Weights one decoder layer multiplies each token by."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def attention_flops(m: dict, ctx: int) -> int:
    """One layer's q.k and p.v for one query over ``ctx`` keys."""
    return 4 * m["num_attention_heads"] * m["head_dim"] * ctx


def token_flops(m: dict, ctx: int, *, head: bool) -> int:
    """Forward FLOPs of one token at 0-based position ``ctx - 1`` through
    every layer, plus the output head where ``head``."""
    L = m["num_hidden_layers"]
    f = L * (2 * layer_matmul_params(m) + attention_flops(m, ctx))
    if head:
        f += 2 * m["hidden_size"] * m["vocab_size"]
    return f


def paged_attention_call(m: dict, positions, itemsize: int = 4) -> tuple[int, int]:
    """(ops, bytes) of one decode-step read of one layer's paged KV for
    live slots at 0-based ``positions``: the K and V of the positions each
    slot attends, read once, plus q and the output."""
    KV, H, hd = (m["num_key_value_heads"], m["num_attention_heads"],
                 m["head_dim"])
    ops = byts = 0
    for p in positions:
        ops += attention_flops(m, p + 1)
        byts += 2 * (p + 1) * KV * hd * itemsize + 2 * H * hd * itemsize
    return ops, byts


def fft_flops(D: int) -> float:
    """A real FFT of length D: 2.5 D log2 D."""
    return 2.5 * D * math.log2(D)


def circconv_call(G: int, R: int, D: int, itemsize: int = 4) -> tuple[float, int]:
    """(ops, bytes) of one bind (or unbind) of G groups of R features of
    width D: R+1 length-D FFTs per group plus the keys' R, and the rows
    in, the keys and the payload out, each moved once."""
    ops = (G * (R + 1) + R) * fft_flops(D)
    byts = (G * R * D + R * D + G * D) * itemsize
    return ops, byts
