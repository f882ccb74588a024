"""Dry-run machinery integration test on a small simulated mesh.

Runs in a subprocess (device count locks at first jax init).  Exercises:
reduced-arch lower+compile with shardings, hloparse roofline extraction,
and the pipeline dry-run path with the codec.
"""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 16) -> dict:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_dryrun_reduced_arch_small_mesh():
    code = textwrap.dedent("""
        import json, dataclasses
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_config, reduced
        from repro.launch import dryrun as dr, hloparse, mesh as mesh_lib
        from repro.models import lm as lm_lib
        from repro.sharding import rules as sh

        mesh = mesh_lib.make_host_mesh(data=4, model=4)
        cfg = reduced(get_config("deepseek-7b"))
        params = lm_lib.abstract_params(cfg, jnp.bfloat16)
        param_sh = sh.param_shardings(params, mesh, mode="train")
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        batch_sh = sh.batch_shardings(batch, mesh)
        opt, train_step = dr.build_train_step(cfg, num_microbatches=2)
        opt_state = jax.eval_shape(opt.init, params)
        opt_sh = sh.opt_state_shardings(opt_state, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(train_step,
                              in_shardings=(param_sh, opt_sh, batch_sh),
                              out_shardings=(param_sh, opt_sh,
                                             NamedSharding(mesh, P()))
                              ).lower(params, opt_state, batch)
            compiled = lowered.compile()
        stats = hloparse.analyze(compiled.as_text())
        mem = compiled.memory_analysis()
        print(json.dumps({
            "flops": stats["dot_flops"],
            "coll": stats["coll_bytes"],
            "peak": int(mem.argument_size_in_bytes + mem.temp_size_in_bytes),
        }))
    """)
    r = run_py(code)
    assert r["flops"] > 1e8       # ~6*N*T/devices with remat (~2.6e8 analytic)
    assert r["coll"] > 0          # TP/FSDP collectives present
    assert 0 < r["peak"] < 32 * 2 ** 30


def test_pipeline_dryrun_compression_ratio_small_mesh():
    code = textwrap.dedent("""
        import json
        import repro.launch.dryrun as dr
        import repro.launch.mesh as mesh_lib
        # shrink the production mesh to the simulated host devices
        mesh_lib.make_production_mesh = \
            lambda multi_pod=False: mesh_lib.make_host_mesh(data=2, model=2, pod=2)
        dr.SHAPES = dict(dr.SHAPES,
                         train_4k=dict(seq_len=64, global_batch=8, kind="train"))
        import dataclasses
        from repro.configs.base import get_config, reduced, register
        small = reduced(get_config("deepseek-7b"))
        import repro.configs.base as base
        base._REGISTRY["tiny"] = lambda: dataclasses.replace(small, name="tiny")
        ident = dr.pipeline_dryrun("tiny", codec_kind="none", num_microbatches=2,
                                   save=False)
        c3 = dr.pipeline_dryrun("tiny", codec_kind="c3sl", R=2,
                                num_microbatches=2, save=False)
        print(json.dumps({"ident": ident["interpod_permute_bytes"],
                          "c3": c3["interpod_permute_bytes"]}))
    """)
    r = run_py(code, devices=8)
    # pair distance on the (2,2,2) mesh is 4, not 256 — just check both ran
    # and produced collective stats
    assert r["ident"] >= 0 and r["c3"] >= 0


def test_collective_parser_pod_distance():
    from repro.launch.dryrun import _pod_permute_bytes
    ln = ("%cp = f32[1,1024]{1,0} collective-permute(%x), channel_id=3, "
          "source_target_pairs={{0,256},{1,257}}")
    assert _pod_permute_bytes(ln) == 1024 * 4
    ln2 = ("%cp = f32[1,1024]{1,0} collective-permute(%x), channel_id=3, "
           "source_target_pairs={{0,1},{1,2}}")
    assert _pod_permute_bytes(ln2) == 0
