"""Plain reference of a llama-style dense decoder split by C3-SL.

Straight ``jax.numpy`` with every matrix product at
``Precision.HIGHEST`` (true float32 on a TPU), no cache, no batching, no
kernels.  It imports nothing of the program: the layer equations are
written out here from the published description of the architecture
(pre-norm RMSNorm residual blocks, rotary embeddings on the first and
second halves of each head, causal softmax attention, SwiGLU MLP, final
RMSNorm, untied head) and C3-SL from the paper (bind R features with fixed
keys by circular convolution and superpose them, unbind by circular
correlation), with the per-row int8 absmax wire stage.

A control is the same computation one step of precision down:
``dtype=bfloat16`` (activations, attention and codec in bfloat16, each
product at the default precision, norms and softmax in float32) with the
weights of :func:`int8_weights` (every matrix int8, one scale per output
channel) below bfloat16 products, or ``precision="high"`` (three
bfloat16 passes) below float32 at ``highest``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "high": jax.lax.Precision.HIGH, "default": None}


class LlamaSplit:
    """``m`` holds the HF config keys; ``cut`` is the number of layers
    before the codec; ``precision`` that of every matrix product."""

    def __init__(self, m: dict, cut: int, dtype=jnp.float32,
                 precision: str = "highest"):
        self.m = m
        self.cut = cut
        self.dtype = jnp.dtype(dtype)
        self.prec = PRECISION[precision]
        self.bottom = jax.jit(self._bottom)
        self.top = jax.jit(self._top)
        self.codec = jax.jit(self._codec)

    # -- pieces --------------------------------------------------------

    def _mm(self, a, b):
        if isinstance(b, tuple):            # int8 values, per-column scales
            q, scale = b
            y = jnp.matmul(a, q.astype(a.dtype), precision=self.prec,
                           preferred_element_type=jnp.float32)
            return (y * scale).astype(self.dtype)
        return jnp.matmul(a, b, precision=self.prec)

    def _embed(self, E, tokens):
        if isinstance(E, tuple):            # int8 rows, per-row scales
            q, scale = E
            return (q[tokens].astype(jnp.float32) * scale[tokens]).astype(
                self.dtype)
        return E[tokens].astype(self.dtype)

    def _norm(self, x, scale):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.m["rms_norm_eps"])
        return (y * scale.astype(jnp.float32)).astype(self.dtype)

    def _rope(self, x, pos):
        """x (T, H, hd): rotate [x1, x2] halves by angle pos * theta^(-2i/hd)."""
        hd = x.shape[-1]
        inv = 1.0 / (self.m["rope_theta"]
                     ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = pos[:, None].astype(jnp.float32) * inv          # (T, hd/2)
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(self.dtype)

    def _attn(self, W, i, h):
        m = self.m
        T = h.shape[0]
        H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
        p = f"layers.{i}."
        x = self._norm(h, W[p + "attn_norm"])
        pos = jnp.arange(T)
        q = self._rope(self._mm(x, W[p + "wq"]).reshape(T, H, hd), pos)
        k = self._rope(self._mm(x, W[p + "wk"]).reshape(T, KV, hd), pos)
        v = self._mm(x, W[p + "wv"]).reshape(T, KV, hd)
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=self.prec)
        s = s.astype(jnp.float32) * hd ** -0.5
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1).astype(self.dtype)
        o = jnp.einsum("hqk,khd->qhd", probs, v, precision=self.prec)
        return self._mm(o.reshape(T, H * hd), W[p + "wo"])

    def _mlp(self, W, i, h):
        p = f"layers.{i}."
        x = self._norm(h, W[p + "mlp_norm"])
        g = self._mm(x, W[p + "w_gate"])
        u = self._mm(x, W[p + "w_up"])
        a = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
        return self._mm(a.astype(self.dtype), W[p + "w_down"])

    def _layers(self, W, h, lo, hi):
        for i in range(lo, hi):
            h = h + self._attn(W, i, h)
            h = h + self._mlp(W, i, h)
        return h

    # -- halves ----------------------------------------------------------

    def _bottom(self, W, tokens):
        """tokens (T,) -> the cut-layer features (T, d)."""
        h = self._embed(W["embed"], tokens)
        return self._layers(W, h, 0, self.cut)

    def _top(self, W, x, rows):
        """Decoded cut features (T, d) -> logits (len(rows), V) at rows."""
        h = self._layers(W, x.astype(self.dtype), self.cut,
                         self.m["num_hidden_layers"])
        h = self._norm(h[rows], W["final_norm"])
        return self._mm(h, W["head"]).astype(jnp.float32)

    # -- C3-SL -----------------------------------------------------------

    def _codec(self, circ, Zg, own):
        """One codec event per row n: Zg (n, R, D) holds the group's
        features by key index (zero where a key's row held no live
        feature); returns what key index ``own`` decodes, (n, D).  A
        request keeps its slot, and so its key, for its whole life.

        bind:   S = sum_i K_i (*) Z_i = sum_i Z_i @ C_i,
                C_i[j, d] = K_i[(d - j) mod D]
        wire:   per-row absmax int8, scale max|S| / 127
        unbind: Zhat_i = K_i (.) S = S @ C_i^T
        """
        Zg = Zg.astype(self.dtype)
        S = jnp.einsum("nrj,rjd->nd", Zg, circ, precision=self.prec)
        S = S.astype(self.dtype)
        scale = jnp.max(jnp.abs(S), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-12)
        S = jnp.round(S / scale) * scale
        return jnp.einsum("nj,dj->nd", S, circ[own],
                          precision=self.prec).astype(self.dtype)


def circulants(keys, dtype=jnp.float32):
    """(R, D, D) with C_i[j, d] = K_i[(d - j) mod D]."""
    D = keys.shape[-1]
    j = jnp.arange(D)[:, None]
    d = jnp.arange(D)[None, :]
    return keys[:, (d - j) % D].astype(dtype)



@functools.partial(jax.jit, static_argnames=("axis",))
def _int8(w, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0,
                        1e-30)
    return jnp.round(w / scale).astype(jnp.int8), scale


def int8_weights(W):
    """Every matrix as (int8 values, float32 scales): absmax per output
    channel, the embedding per row (it is read by rows); vectors stay.
    Leaf by leaf, so no more than one float32 matrix is made at a time."""
    return {n: _int8(w, axis=1 if n == "embed" else 0) if w.ndim == 2 else w
            for n, w in W.items()}
