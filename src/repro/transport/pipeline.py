"""2-stage pod pipeline over the transport layer (compressed ppermute wire).

Moved here from ``repro.core.split`` and extended two ways:

* **Per-direction codecs** — the channel accepts a static ``SplitLink``;
  an asymmetric link inserts the gradient seam on the payload, so the
  gradient crossing the pod boundary is degraded/accounted as the backward
  channel's own codec/R.  Like every wire stage in this repo (int8, topk),
  the seam is a straight-through MODEL: the in-graph adjoint tensor keeps
  the forward payload's (mb/R_fwd, D) shape — the measured HLO
  collective-permute bytes do not shrink — while ``wire_bytes_bwd``
  accounts what the re-grouped (mb/(R_fwd*R_bwd), D) payload would ship,
  and the reconstruction noise of that round-trip is applied for real.

* **Asynchronous (double-buffered) channel** — ``async_depth`` sizes a ring
  of in-flight payload buffers in the ``lax.scan`` carry.  ``async_depth=1``
  is the synchronous PR-4 schedule bit-identically (one buffer: the payload
  sent at step t is consumed at t+1, the scan serializes send→consume).
  ``async_depth=2`` consumes the payload sent at step t-2, so the ppermute
  of microbatch t's payload has the whole of step t+1's front-pass compute
  to complete in — the send overlaps the next microbatch's forward work
  instead of sitting on the scan's critical path.

  Staleness semantics (well-defined, pinned in tests/test_pipeline_async.py):
  the payload of microbatch m is consumed by the back stage at scan step
  m + depth and paired with ITS OWN labels y_m — the skew delays
  consumption, it never mis-pairs microbatches — so the loss and gradients
  are identical to the synchronous schedule; the cost is depth-1 extra
  bubble steps (the scan runs M + depth steps) and depth payload buffers
  resident in the carry.

Pipeline schedule (M = num_microbatches, d = async_depth, steps t = 0..M+d-1):
    pod0:  front(mb_t)          for t < M
    pod1:  back(recv_{t-d})     for t >= d
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.codecs import AdaptiveC3SL
from repro.transport.channel import grad_roundtrip, masked_decode
from repro.transport.link import SplitLink


def _require_static(codec):
    chans = (codec.fwd.codec, codec.bwd.codec) if isinstance(codec, SplitLink) \
        else (codec,)
    for c in chans:
        if isinstance(c, AdaptiveC3SL):
            raise ValueError(
                "the pod pipeline compiles ONE program; resolve adaptive "
                "channels to static buckets first (transport.pin_link / "
                "AdaptiveC3SL.current) — see repro.launch.train.run_pipeline")


def make_pod_pipeline_loss_fn(
    embed_fn: Callable,        # (embed_params, x_mb) -> h (mb, S, E)
    stage_fn: Callable,        # (stage_blocks, h) -> h  (one stage's blocks; same fn both stages)
    head_loss_fn: Callable,    # (head_params, h, y_mb) -> scalar mean loss
    codec,                     # flat codec OR static SplitLink
    mesh,
    num_microbatches: int = 1,
    async_depth: int = 1,
    with_erasure: bool = False,
) -> Callable:
    """Returns loss(params, batch) implementing the 2-stage compressed pipeline.

    params = {"embed", "blocks" (leading stage axis 2, sharded P("pod")),
              "head", "codec"}.
    batch  = {"x": (B, S) or (B, S, E_in), "y": (B, S)} — replicated over pod,
             sharded over data on the batch dim by the caller.

    The in-flight payloads are a ring of ``async_depth`` lax.scan carry
    buffers; ``lax.ppermute`` moves the newest one each step (see module
    docstring for the schedule and staleness semantics).

    ``with_erasure=True`` compiles the chaos variant instead:
    ``loss(params, batch, keep)`` where ``keep`` is an
    ``(M + depth, mb // R_fwd, D)`` float32 stack of per-step keep masks
    — ``keep[t]`` masks the payload the back stage CONSUMES at scan step
    t (the one sent at t - depth), decoded through the renormalizing
    ``decode_masked`` path.  An all-ones stack reproduces the clean
    schedule bitwise (the masked decode is exact at full mask); the
    erasure-free builder keeps the exact pre-fault trace.
    """
    M = num_microbatches
    depth = int(async_depth)
    if depth < 1:
        raise ValueError(f"async_depth must be >= 1, got {async_depth}")
    _require_static(codec)
    link = codec if isinstance(codec, SplitLink) else None
    fwd_codec = link.fwd.codec if link is not None else codec

    def loss(params, batch, keep=None):
        if with_erasure and keep is None:
            raise ValueError(
                "with_erasure=True compiles the masked consume path: pass "
                "the (M + depth, rows, D) keep-mask stack (all-ones for a "
                "loss-free step)")
        if not with_erasure and keep is not None:
            raise ValueError("keep masks need the with_erasure=True builder")

        def inner(x, y, embed_p, blocks_local, head_p, codec_p, *rest):
            keep_stack = rest[0] if rest else None
            stage = jax.lax.axis_index("pod")
            # blocks_local: (1, L/2, ...) — this pod's stage blocks
            my_blocks = jax.tree.map(lambda a: a[0], blocks_local)
            fwd_p = link.fwd_params(codec_p) if link is not None else codec_p

            B = x.shape[0]
            assert B % M == 0, (B, M)
            mb = B // M
            x_mbs = x.reshape(M, mb, *x.shape[1:])
            y_mbs = y.reshape(M, mb, *y.shape[1:])

            h_probe = embed_fn(embed_p, x_mbs[0])
            flat_shape = (mb, h_probe.shape[1] * h_probe.shape[2])

            def payload_of(h):
                payload = fwd_codec.encode(fwd_p, h.reshape(flat_shape))
                if link is not None and not link.mirrored:
                    # gradient seam: the cotangent crossing back through
                    # the pod boundary is round-tripped (straight-through,
                    # shape-preserving) by the backward channel's codec —
                    # in SPMD both pods run the same program, so which side
                    # of the reverse ppermute applies it is equivalent
                    payload = grad_roundtrip(link.bwd.codec, payload,
                                             link.bwd_params(codec_p))
                # shard the wire tensor over (data, model) BEFORE the pod
                # hop: the FFT encode otherwise leaves D replicated and every
                # model shard would redundantly send the full payload.
                # (scatter is intra-pod ICI — cheap; the pod link is scarce)
                from repro.sharding.constraints import constrain
                return constrain(payload, ("data", "model"))

            def step(bufs, t):
                # input for my stage at step t; the back stage consumes the
                # OLDEST in-flight buffer (sent depth steps ago = microbatch
                # t - depth) and pairs it with that microbatch's labels
                x_t = jax.lax.dynamic_index_in_dim(
                    x_mbs, jnp.minimum(t, M - 1), axis=0, keepdims=False)
                y_prev = jax.lax.dynamic_index_in_dim(
                    y_mbs, jnp.clip(t - depth, 0, M - 1), axis=0,
                    keepdims=False)
                h_front_in = embed_fn(embed_p, x_t)
                if keep_stack is None:
                    h_back = fwd_codec.decode(fwd_p, bufs[-1])
                else:
                    keep_t = jax.lax.dynamic_index_in_dim(
                        keep_stack, t, axis=0, keepdims=False)
                    h_back = masked_decode(fwd_codec, fwd_p, bufs[-1],
                                           keep_t)
                h_back_in = h_back.reshape(h_front_in.shape)
                h_in = jnp.where(stage == 0, h_front_in, h_back_in)
                h_out = stage_fn(my_blocks, h_in)
                payload = payload_of(h_out)
                # channel: stage0 -> stage1 (stage1's payload goes back to 0
                # and is ignored, closing the permutation ring)
                recv = jax.lax.ppermute(payload, "pod", perm=[(0, 1), (1, 0)])
                mb_loss = head_loss_fn(head_p, h_out, y_prev)
                valid = jnp.logical_and(stage == 1, t >= depth)
                # per-step losses ride the scan ys (not a scalar carry): the
                # masked-out warmup/front-stage entries are exact zeros
                return (recv,) + bufs[:-1], jnp.where(valid, mb_loss, 0.0)

            payload0 = jnp.zeros_like(payload_of(h_probe))
            bufs0 = (payload0,) * depth
            _, step_losses = jax.lax.scan(step, bufs0, jnp.arange(M + depth))
            # only pod1 accumulated loss; sum over pods and average microbatches
            return jax.lax.psum(step_losses.sum(), "pod") / M

        args = (batch["x"], batch["y"], params["embed"], params["blocks"],
                params["head"], params["codec"])
        specs = (P(), P(), P(), P("pod"), P(), P())
        if with_erasure:
            args += (keep,)
            specs += (P(),)
        # manual over "pod" only: data/model stay under automatic sharding
        return jax.shard_map(inner, mesh=mesh, in_specs=specs, out_specs=P(),
                             axis_names={"pod"}, check_vma=False)(*args)

    return loss
