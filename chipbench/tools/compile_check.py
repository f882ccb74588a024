"""Compile a serve configuration's engine programs for a described TPU
v5e, without the chip, and print their ``memory_analysis()``.

    JAX_PLATFORMS=cpu python chipbench/tools/compile_check.py deepseek-7b-split-serve

The TPU compiler refuses a program that does not fit the chip's memory or
a kernel that needs more VMEM than it may use, so this shows before any
chip time is spent whether the window (decode) and prefill programs of
the configuration compile, and how many bytes each needs.  Shapes only:
no weight or cache is allocated.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(name: str) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import circconv
    from repro.models import lm as lm_lib
    from chipbench.drivers import serve

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    circconv._interpret = lambda: False
    cfg = serve.model_config(conf)
    params = jax.eval_shape(lambda k: lm_lib.init_lm_params(k, cfg),
                            jax.random.PRNGKey(0))
    real_init = lm_lib.init_decode_cache
    lm_lib.init_decode_cache = lambda *a, **k: jax.eval_shape(
        lambda: real_init(*a, **k))
    try:
        keys = jnp.zeros((conf["link"]["R"], cfg.d_model), jnp.float32)
        eng = serve.make_engine(conf, params, keys, 0)
    finally:
        lm_lib.init_decode_cache = real_init

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    e = conf["engine"]
    B, C, W = e["num_slots"], e["chunk_size"], eng._window_len
    progs = eng._programs[None]
    p, c = on_chip(params), on_chip(eng.cache)
    st = on_chip(jax.eval_shape(lambda: eng.state))
    out = {}
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    keys_w = on_chip(jax.ShapeDtypeStruct((W, 2), jnp.uint32))
    lowered = {
        "window": progs["window"].lower(
            p, c, st, keys_w, on_chip(jax.ShapeDtypeStruct((), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((), jnp.bool_))),
        "prefill": progs["prefill"].lower(
            p, c, st, on_chip(jax.ShapeDtypeStruct((B, C), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((B, C), jnp.bool_)),
            on_chip(jax.ShapeDtypeStruct((B,), jnp.bool_)), key),
    }
    for prog, low in lowered.items():
        compiled = low.compile()
        mem = compiled.memory_analysis()
        out[prog] = {"argument_bytes": int(mem.argument_size_in_bytes),
                     "temp_bytes": int(mem.temp_size_in_bytes),
                     "output_bytes": int(mem.output_size_in_bytes),
                     "alias_bytes": int(mem.alias_size_in_bytes),
                     "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1]), indent=1))
