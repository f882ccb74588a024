"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed with JAX and compiles for
a topology that is described, not attached.  What interpret mode cannot
show — the (8, 128) tiling rule, the VMEM limit, primitives Mosaic cannot
lower — the compiler refuses here.  Shapes are deepseek-7b's published
widths at the serving engine's geometry: 8 slots, 512 positions, 16-token
pages, and the ``c3sl:R=4`` cut codec (8 slots / R = 2 groups of D=4096).

The serving programs themselves (decode window and prefill chunk, paged
with the kernel read and the Pallas codec at the cut) are compiled at a
small geometry to check that the KV pools are updated in place.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import re

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


# the serving programs at a small geometry whose KV pools outweigh
# everything else the programs hold: 4 layers, 8 heads of 128, 1024 pages
SERVE_POOL = (4, 1024, 16, 8, 128)               # (L, num_pages, ps, KV, hd)
POOL_COPY = re.compile(
    r"stablehlo\.(concatenate|slice|dynamic_slice|dynamic_update_slice)\b"
    r".*-> tensor<((?:\d+x)*)16x8x128x\w+>")


def _pool_sized(lead: str) -> bool:
    n = 1
    for d in filter(None, lead.split("x")):
        n *= int(d)
    return n >= SERVE_POOL[1]


@pytest.mark.filterwarnings("ignore:kv_read='kernel'")
def test_serving_programs_update_pools_in_place(one_chip, compiled_kernels,
                                                monkeypatch):
    """The decode window and the prefill chunk, paged with the kernel read
    and the Pallas C3-SL codec at the cut, keep each KV pool in one buffer:
    no split, concatenate, per-layer slice or update of a pool is lowered,
    and neither program needs as many temporary bytes as one pool leaf."""
    from repro.configs.base import ModelConfig
    from repro.models import lm as lm_lib
    from repro.serving.engine import BatchedEngine
    L, N, ps, KV, hd = SERVE_POOL
    cfg = ModelConfig(name="serve-pools", family="dense", num_layers=L,
                      d_model=KV * hd, num_heads=KV, num_kv_heads=KV,
                      d_ff=256, vocab_size=256, head_dim=hd,
                      block_pattern=(("attn", "mlp"),))
    params = jax.eval_shape(lambda k: lm_lib.init_lm_params(k, cfg),
                            jax.random.PRNGKey(0))
    real_init = lm_lib.init_decode_cache
    monkeypatch.setattr(lm_lib, "init_decode_cache", lambda *a, **k:
                        jax.eval_shape(lambda: real_init(*a, **k)))
    slots, chunk, R = 8, 64, 4
    eng = BatchedEngine(params, cfg, num_slots=slots, max_len=512,
                        codec=f"c3sl:R={R},backend=pallas|int8",
                        codec_params={"keys": jnp.zeros((R, cfg.d_model))},
                        chunk_size=chunk, sync_every=4, kv_layout="paged",
                        page_size=ps, num_pages=N, kv_read="kernel")
    assert eng.cache["stack"]["l0_0_attn"]["k"].shape == SERVE_POOL

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    progs = eng._programs[None]
    p, c = on_chip(params), on_chip(eng.cache)
    st = on_chip(jax.eval_shape(lambda: eng.state))
    lowered = {
        "window": progs["window"].lower(
            p, c, st, _spec(one_chip, (eng._window_len, 2), jnp.uint32),
            _spec(one_chip, (), jnp.int32), _spec(one_chip, (), jnp.bool_)),
        "prefill": progs["prefill"].lower(
            p, c, st, _spec(one_chip, (slots, chunk), jnp.int32),
            _spec(one_chip, (slots, chunk), jnp.bool_),
            _spec(one_chip, (slots,), jnp.bool_),
            _spec(one_chip, (2,), jnp.uint32)),
    }
    leaf_bytes = 4 * L * N * ps * KV * hd
    for name, low in lowered.items():
        copies = [m.group(0)[:160] for m in POOL_COPY.finditer(low.as_text())
                  if _pool_sized(m.group(2))]
        assert not copies, (name, copies)
        compiled = low.compile()
        assert "tpu_custom_call" in compiled.as_text()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < leaf_bytes, (name, temp, leaf_bytes)
