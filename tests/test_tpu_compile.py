"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed with JAX and compiles for
a topology that is described, not attached.  What interpret mode cannot
show — the (8, 128) tiling rule, the VMEM limit, primitives Mosaic cannot
lower — the compiler refuses here.  Shapes are deepseek-7b's published
widths at the serving engine's geometry: 8 slots, 512 positions, 16-token
pages, and the ``c3sl:R=4`` cut codec (8 slots / R = 2 groups of D=4096).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import circconv, paged_attention as pa

H = KV = 32
HD, PS, T, B = 128, 16, 512, 8
D, R = 4096, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here; the described chip takes the compiled path
    monkeypatch.setattr(circconv, "_interpret", lambda: False)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["bind", "unbind"])
def test_circconv_compiles_for_v5e(one_chip, compiled_kernels, op):
    G = B // R
    keys = _spec(one_chip, (R, D))
    if op == "bind":
        _assert_kernel(circconv.bind_superpose_kernel,
                       _spec(one_chip, (G, R, D)), keys)
    else:
        _assert_kernel(circconv.unbind_kernel, _spec(one_chip, (G, D)), keys)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
def test_paged_attention_compiles_for_v5e(one_chip, compiled_kernels, dtype):
    P = T // PS
    N = B * P
    q = _spec(one_chip, (B, 1, H, HD),
              jnp.float32 if dtype == jnp.int8 else dtype)
    pool = _spec(one_chip, (N, PS, KV, HD), dtype)
    table = _spec(one_chip, (B, P), jnp.int32)
    pos = _spec(one_chip, (B,), jnp.int32)
    if dtype == jnp.int8:
        scales = _spec(one_chip, (N, PS, KV, 1))
        _assert_kernel(lambda q, k, ks, v, vs, t, p: pa.paged_attention_quant(
            q, k, ks, v, vs, t, p, length=T), q, pool, scales, pool, scales,
            table, pos)
    else:
        _assert_kernel(lambda q, k, v, t, p: pa.paged_attention(
            q, k, v, t, p, length=T), q, pool, pool, table, pos)
