"""The paper's topology at datacenter scale: 2-stage pod pipeline where the
transport layer compresses the inter-pod channel (ppermute) in BOTH
directions — each direction with its OWN codec (the backward gradient
payload re-grouped by the ``bwd:`` channel), and the channel double-buffered
(``async_depth=2``) so microbatch t's payload send overlaps microbatch
t+1's front pass.

    PYTHONPATH=src python examples/pod_split_pipeline.py

Runs on 8 simulated host devices as a (pod=2, data=2, model=2) mesh; prints
the loss curve and the per-direction channel-bytes saving vs uncompressed.
This is the runnable small-scale twin of the production (2,16,16) dry-run.
"""
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro import transport
from repro.configs.base import get_config, reduced
from repro.launch import mesh as mesh_lib
from repro.models import lm as lm_lib
from repro.data.pipeline import SyntheticTokenDataset
from repro.optim import adamw, apply_updates, clip_by_global_norm

STEPS = int(os.environ.get("PIPELINE_STEPS", 30))
ASYNC_DEPTH = int(os.environ.get("PIPELINE_ASYNC_DEPTH", 2))


def main():
    cfg = reduced(get_config("deepseek-7b"), num_layers=4, d_model=128,
                  d_ff=256, vocab_size=128, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    mesh = mesh_lib.make_host_mesh(data=2, model=2, pod=2)
    B, S, M, R = 32, 32, 4, 4     # mb=8: fwd R=4 leaves 2 gradient rows
    mb = B // M                   # for the bwd channel's R=2 grouping
    # forward: R=4 + int8 wire; backward: the gradient payload (mb/R rows)
    # re-grouped by its own R=2 — the per-direction transport link
    codec = transport.build_link(
        f"c3sl:R={min(R, mb)}|int8 >> bwd:c3sl:R=2|int8", D=S * cfg.d_model)

    rng = jax.random.PRNGKey(0)
    full = lm_lib.init_lm_params(rng, cfg)
    params = {
        "embed": {"embed": full["embed"]},
        "blocks": lm_lib.split_stack_for_pipeline(full["stack"]),
        "head": {"final_norm": full["final_norm"], "head": full["head"]},
        "codec": codec.init(jax.random.PRNGKey(7)),
    }
    embed_fn, stage_fn, head_loss_fn = lm_lib.make_pipeline_fns(cfg)
    loss_fn = transport.make_pod_pipeline_loss_fn(
        embed_fn, stage_fn, head_loss_fn, codec, mesh, num_microbatches=M,
        async_depth=ASYNC_DEPTH)

    opt = adamw(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, _ = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    data = SyntheticTokenDataset(cfg.vocab_size, S, seed=0)
    losses = []
    with jax.set_mesh(mesh):
        for i in range(STEPS):
            b = data.batch(B, i)
            params, opt_state, loss = step(
                params, opt_state, {"x": b["tokens"], "y": b["labels"]})
            losses.append(float(loss))
            if i % 5 == 0:
                print(f"step {i:3d} loss {losses[-1]:.4f}")

    wf = codec.wire_bytes_fwd(mb)
    wb = codec.wire_bytes_bwd(mb)
    base = mb * S * cfg.d_model * 4
    print(f"\nloss {losses[0]:.3f} -> {losses[-1]:.3f}")
    print(f"inter-pod bytes per microbatch (async_depth={ASYNC_DEPTH}): "
          f"fwd {wf:,} + bwd {wb:,} vs {2 * base:,} uncompressed "
          f"({2 * base / (wf + wb):.1f}x)")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
