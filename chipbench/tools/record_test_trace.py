"""Record the small trace the trace-reduction test reads, on the chip.

    python chipbench/tools/record_test_trace.py <out dir>

A few steps of a matrix product, a Pallas kernel and a host pause, each
inside a named host span, traced by the JAX profiler.  Writes the
``.xplane.pb`` under ``<out dir>`` and a JSON of what the host measured
beside it.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path


def main(out: Path) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.profiler import TraceAnnotation

    def add_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    @jax.jit
    def step(a, b):
        c = a @ b
        return pl.pallas_call(add_kernel,
                              out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
                              name="test_add_kernel")(c)

    a = jnp.ones((1024, 1024), jnp.float32)
    b = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(step(a, b))
    jax.profiler.start_trace(str(out))
    t0 = time.monotonic()
    for i in range(3):
        with TraceAnnotation("test.step"):
            jax.block_until_ready(step(a, b))
        with TraceAnnotation("test.pause"):
            time.sleep(0.02)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    (out / "host.json").write_text(json.dumps({"window_s": t1 - t0, "steps": 3,
                                                "pause_s": 0.02}))


if __name__ == "__main__":
    main(Path(sys.argv[1]))
