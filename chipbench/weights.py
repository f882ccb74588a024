"""Seeded weights and codec keys, made by the benchmark and not the program.

Every tensor has a canonical name and is drawn from a key folded from the
run's seed and that name, so the program's copy (made on the device in one
jitted call) and the reference's copy (made again after the program's
state is freed) are the same numbers without either taking anything from
the other.

Scales follow the usual fan-in rule: a projection from ``n`` inputs is
N(0, 1/n); the embedding is N(0, 1/d); norm scales are one.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def llama_shapes(m: dict) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """name -> (shape, std) of a llama-style dense decoder whose sizes are
    the HF config keys in ``m``; std None means a norm scale of ones."""
    d, ff, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    out = {"embed": ((V, d), d ** -0.5),
           "final_norm": ((d,), None),
           "head": ((d, V), d ** -0.5)}
    for i in range(m["num_hidden_layers"]):
        p = f"layers.{i}."
        out.update({
            p + "attn_norm": ((d,), None),
            p + "wq": ((d, H * hd), d ** -0.5),
            p + "wk": ((d, KV * hd), d ** -0.5),
            p + "wv": ((d, KV * hd), d ** -0.5),
            p + "wo": ((H * hd, d), (H * hd) ** -0.5),
            p + "mlp_norm": ((d,), None),
            p + "w_gate": ((d, ff), d ** -0.5),
            p + "w_up": ((d, ff), d ** -0.5),
            p + "w_down": ((ff, d), ff ** -0.5),
        })
    return out


def tensor(key, name: str, shape, std, dtype=jnp.float32):
    if std is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def llama_weights(key, m: dict, dtype=jnp.float32) -> dict:
    """All canonical tensors (call under jit so they are made on the
    device in one program)."""
    return {n: tensor(key, n, s, std, dtype)
            for n, (s, std) in llama_shapes(m).items()}


def hrr_keys(key, R: int, D: int):
    """C3-SL's fixed keys: N(0, 1/D), each scaled to unit norm (the
    paper's sampler)."""
    k = jax.random.normal(jax.random.fold_in(key, zlib.crc32(b"c3sl.keys")),
                          (R, D), jnp.float32) * D ** -0.5
    return k / jnp.linalg.norm(k, axis=-1, keepdims=True)
