"""Training driver (runs for real on whatever devices exist; CPU-friendly).

Examples:
    # reduced-config LM training with the C3-SL boundary codec (registry
    # spec string; see repro.codecs for the grammar)
    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --reduced \
        --steps 50 --batch 16 --seq 128 --codec "c3sl:R=4"

    # int8 wire format composed behind the HRR transform
    PYTHONPATH=src python -m repro.launch.train --reduced --steps 2 \
        --codec "c3sl:R=4|int8"

    # Adaptive-R: SNR-driven schedule over a {2,4,8,16} bucket ladder; the
    # loop logs per-step R + wire bytes and compiles one branch per bucket
    PYTHONPATH=src python -m repro.launch.train --reduced --steps 50 \
        --codec "adaptive:c3sl:R=16,min_R=2,target_snr=-6|int8"

    # 2-stage pod pipeline on a host mesh (needs >= 2 devices: set
    # XLA_FLAGS=--xla_force_host_platform_device_count=2)
    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --reduced \
        --pipeline --microbatches 4 --steps 20
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro import codecs, transport
from repro.checkpoint import save_checkpoint
from repro.configs.base import get_config, reduced
from repro.data.pipeline import SyntheticTokenDataset, make_batch_iterator
from repro.launch import mesh as mesh_lib
from repro.launch.runtime import configure_jax
from repro.models import lm as lm_lib
from repro.optim import adamw, apply_updates, clip_by_global_norm
from repro.transport import pipeline as pipeline_lib


def make_codec(spec: str, D: int, *, R: int = 4, quant=None, unitary=False,
               max_R: int | None = None):
    """Build (codec-or-link, params) from a registry spec string.

    ``spec == "none"`` means no codec at all.  A ``... >> bwd:...`` spec
    builds a per-direction ``repro.transport.SplitLink`` (the backward
    gradient payload gets its own codec/R).  The legacy --R/--quant/
    --unitary flags act as defaults for spec-omitted fields (explicit spec
    args win; --quant 8 appends the int8 wire stage to plain specs).
    """
    if spec in (None, "", "none"):
        return None, None
    codec = transport.build_link_or_codec(spec, quant_bits=quant, D=D, R=R,
                                          unitary=unitary)
    if max_R is not None:
        codec = codecs.clamp_R(codec, max_R)
    return codec, codec.init(jax.random.PRNGKey(7))


def _arm_train_sanitizers(args):
    """The --sanitize tier for the train loops: global NaN trap, checkify
    float checks compiled into every step branch, and per-step host-side
    finite checks.  Returns None when sanitize mode is off."""
    if not getattr(args, "sanitize", False):
        return None
    from repro.analysis import sanitize as sanitize_lib
    sanitize_lib.enable_debug_nans()
    print("[sanitize] debug_nans + checkify float checks + per-step "
          "finite checks armed", flush=True)
    return sanitize_lib


def run_standard(args, cfg):
    sanitize_lib = _arm_train_sanitizers(args)
    rng = jax.random.PRNGKey(args.seed)
    params = lm_lib.init_lm_params(rng, cfg)
    opt = adamw(args.lr)
    opt_state = opt.init(params)
    # R clamps to the batch BEFORE init (matching serve.py): batch-wise
    # grouping needs R | batch, and an adaptive ladder must not be able to
    # ramp to a bucket that would fail the divisibility check mid-training
    codec, codec_params = make_codec(args.codec, args.seq * cfg.d_model,
                                     R=args.R, quant=args.quant,
                                     unitary=args.unitary, max_R=args.batch)
    # make_codec returns a SplitLink only for ' >> bwd:' specs, which are
    # always asymmetric — mirrored behavior is just the bare-codec path
    link = codec if isinstance(codec, transport.SplitLink) else None
    adaptive = isinstance(codec, codecs.AdaptiveC3SL)
    adaptive_bwd = link is not None and link.bwd.adaptive

    # Seeded fault injection on the cut link (the CI chaos-smoke job): a
    # FaultPlan draws per-step packet loss on the boundary payload, the
    # RecoveryPolicy decides erasure-tolerant decode vs NACK/retransmit.
    # Clean runs (no fault flags) never touch this path — the compiled
    # programs are bit-identical to pre-fault builds.
    fault_link = None
    if args.fault_drop > 0.0 or args.fault_corrupt > 0.0:
        if codec is None:
            raise SystemExit("--fault-drop/--fault-corrupt need a boundary "
                             "codec (--codec): a raw split has no payload "
                             "to lose")
        plan = transport.FaultPlan(
            seed=args.fault_seed,
            rates={"drop": args.fault_drop, "corrupt": args.fault_corrupt})
        fault_link = link if link is not None else transport.as_link(codec)
        fault_link.install_faults(
            plan, transport.RecoveryPolicy(mode=args.fault_mode))
        print(f"[faults] installed on the cut link: drop={args.fault_drop} "
              f"corrupt={args.fault_corrupt} seed={args.fault_seed} "
              f"recovery={args.fault_mode}", flush=True)

    def make_step(step_codec, step_codec_params):
        """One jitted train step closing over ONE static codec/link + its
        params.  Under Adaptive-R this is called once per (R_fwd, R_bwd)
        bucket pair — each pair is its own compiled branch, so host-side
        schedule switches never retrace.  The probe argument taps the
        gradient-retrieval SNR (asymmetric links; zero otherwise).  With
        faults installed the step takes the erasure keep-masks as a runtime
        argument (bucket-static shapes — masked steps share the branch)."""
        def _body(params, opt_state, batch, probe, erasure):
            def loss_fn(p, pr):
                return lm_lib.lm_loss(p, batch, cfg, codec=step_codec,
                                      codec_params=step_codec_params,
                                      with_metrics=True, bwd_probe=pr,
                                      erasure=erasure)
            (loss, metrics), (grads, bwd_snr) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, probe)
            grads, gn = clip_by_global_norm(grads, 1.0)
            updates, opt_state2 = opt.update(grads, opt_state, params)
            return (apply_updates(params, updates), opt_state2, loss, gn,
                    metrics.get("cut_snr"), bwd_snr)
        fn = _body if fault_link is not None \
            else functools.partial(_body, erasure=None)
        if sanitize_lib is not None:
            # each bucket branch compiles WITH checkify's float checks;
            # the wrapper throws host-side on the first NaN/Inf/div0
            return sanitize_lib.checkify_jit(fn)
        return jax.jit(fn)

    step_fns = transport.build_link_program_table(codec, codec_params,
                                                  make_step)
    train_san = sanitize_lib.TrainSanitizer() if sanitize_lib else None

    data = SyntheticTokenDataset(cfg.vocab_size, args.seq, seed=args.seed)
    it = make_batch_iterator(data, args.batch)
    t0 = time.time()
    losses = []
    wire_fwd_total = wire_bwd_total = 0
    fault_skipped = 0
    probe0 = jnp.float32(0.0)
    tokens_per_step = args.batch * args.seq
    # MFU denominator: this host's measured-equivalent peak (CPU has no
    # published peak; report model-FLOPs throughput instead)
    step_flops = 6.0 * cfg.active_param_count() * tokens_per_step
    for step in range(args.steps):
        batch = next(it)
        if cfg.frontend:
            batch["frontend"] = jnp.zeros(
                (args.batch, cfg.frontend_seq, cfg.frontend_dim))
        erasure = fault_info = None
        if fault_link is not None:
            try:
                erasure, fault_info = fault_link.next_erasure(args.batch)
            except transport.ChannelErasure as e:
                # this step's payload is unrecoverable under the policy's
                # retry budget — skip it rather than train on garbage
                fault_skipped += 1
                print(f"step {step:5d} SKIPPED (unrecoverable): {e}",
                      flush=True)
                continue
        key = transport.link_program_key(codec)
        if fault_link is None:
            params, opt_state, loss, gn, snr, bwd_snr = step_fns[key](
                params, opt_state, batch, probe0)
        else:
            params, opt_state, loss, gn, snr, bwd_snr = step_fns[key](
                params, opt_state, batch, probe0, erasure)
        losses.append(loss)       # device value; one sync after the loop
        if train_san is not None:
            train_san.check_step(step, loss=loss, gnorm=gn)
        # actual bytes this step put on the boundary, per direction: the
        # backward payload has the forward's compressed shape (mirrored /
        # bare codecs) or its own channel's wire format (asymmetric links)
        if codec is None:
            wf = wb = 0
        elif link is not None:
            wf = link.wire_bytes_fwd(args.batch)
            wb = link.wire_bytes_bwd(args.batch)
        else:
            step_codec = codec.buckets[key] if adaptive else codec
            wf = wb = step_codec.wire_bytes(args.batch)
        if fault_info is not None:
            # retransmissions inflate the actual wire traffic
            if fault_info.get("fwd"):
                wf = int(round(wf * fault_info["fwd"]["wire_mult"]))  # lint-ok: R3 host ints from the fault schedule, no device value
            if fault_info.get("bwd"):
                wb = int(round(wb * fault_info["bwd"]["wire_mult"]))  # lint-ok: R3 host ints from the fault schedule, no device value
        wire_fwd_total += wf
        wire_bwd_total += wb
        if link is not None:
            link.observe(fwd_snr=float(snr) if snr is not None else None,  # lint-ok: R3 adaptive controller is host-side by design: it must see this step's SNR before the next dispatch
                         bwd_snr=(float(bwd_snr) if adaptive_bwd else None))  # lint-ok: R3 adaptive controller is host-side by design
        elif adaptive:
            codec.observe(float(snr))      # EMA + ladder walk for NEXT step  # lint-ok: R3 adaptive controller is host-side by design
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            tps = tokens_per_step * (step + 1) / dt
            sched = ""
            if codec is not None:
                sched = f" wire fwd {wf:,d}B + bwd {wb:,d}B /step"
                if link is not None:
                    # static channels keep a constant R; adaptive ones show
                    # the bucket that SERVED this step (the dispatch key)
                    rf = key[0] if key[0] is not None \
                        else getattr(link.fwd.codec, "R", 1)
                    rb = key[1] if key[1] is not None \
                        else getattr(link.bwd.codec, "R", 1)
                    sched = (f" R={rf}>>bwd:{rb}"
                             f" snr {float(snr):.1f}dB"  # lint-ok: R3 log-gated (log_every cadence)
                             f" grad-snr {float(bwd_snr):.1f}dB" + sched)  # lint-ok: R3 log-gated (log_every cadence)
                elif adaptive:
                    sched = (f" R={key} snr {float(snr):.1f}dB "  # lint-ok: R3 log-gated (log_every cadence)
                             f"(ema {codec.ema_snr:.1f})" + sched)
                elif snr is not None:
                    sched = f" snr {float(snr):.1f}dB" + sched  # lint-ok: R3 log-gated (log_every cadence)
                if fault_info is not None and fault_info.get("fwd"):
                    fi = fault_info["fwd"]
                    sched += (f" [erased {fi['erased_frac']:.0%} "
                              f"x{fi['wire_mult']:.2f} wire]")
            print(f"step {step:5d} loss {float(loss):.4f} gnorm {float(gn):.3f}"  # lint-ok: R3 log-gated (log_every cadence)
                  f"{sched} | {tps:,.0f} tok/s, "
                  f"{step_flops*(step+1)/dt/1e9:.1f} "
                  f"GFLOP/s model-flops ({dt:.1f}s)", flush=True)
    # single deferred device->host sync for the whole run: the per-step
    # float(loss) serialized every dispatch with the previous step's compute
    losses = [float(l) for l in losses]
    if codec is not None:
        print(f"boundary traffic: {wire_fwd_total:,d} B fwd + "
              f"{wire_bwd_total:,d} B bwd = "
              f"{wire_fwd_total + wire_bwd_total:,d} B total over "
              f"{args.steps} steps", flush=True)
    if fault_link is not None:
        print(f"[faults] {fault_skipped} of {args.steps} steps skipped as "
              f"unrecoverable", flush=True)
        if not losses:
            raise SystemExit("[faults] every step was unrecoverable — "
                             "raise the retry budget or lower the rates")
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, {"params": params},
                        {"arch": cfg.name, "loss": losses[-1]})
    return losses


def run_pipeline(args, cfg):
    """2-stage pod pipeline with the compressed channel (repro.core.split)."""
    sanitize_lib = _arm_train_sanitizers(args)
    n_dev = len(jax.devices())
    assert n_dev >= 2 and n_dev % 2 == 0, \
        "pipeline mode needs an even device count (set --xla_force_host_platform_device_count)"
    mesh = mesh_lib.make_host_mesh(data=n_dev // 2, model=1, pod=2)

    rng = jax.random.PRNGKey(args.seed)
    full = lm_lib.init_lm_params(rng, cfg)
    # R is clamped to the microbatch size BEFORE init so the key shapes match
    mb = args.batch // args.microbatches
    codec, codec_params = make_codec(
        args.codec, args.seq * cfg.d_model, R=args.R, quant=args.quant,
        unitary=args.unitary, max_R=mb)
    if codec is None:
        codec = codecs.build("identity", D=args.seq * cfg.d_model)
        codec_params = {}
    if isinstance(codec, transport.SplitLink):
        if codec.fwd.adaptive or codec.bwd.adaptive:
            # the pipeline's scan/shard_map closes over ONE codec pair —
            # pin both channels at their current buckets rather than
            # silently baking whatever was current at trace time
            print(f"[pipeline] adaptive link pinned at "
                  f"R={codec.fwd.current_R}>>bwd:{codec.bwd.current_R} "
                  f"(per-step adaptation needs the single-program path)",
                  flush=True)
            codec_params = transport.slice_link_params(codec, codec_params)
            codec = transport.pin_link(codec)
    elif isinstance(codec, codecs.AdaptiveC3SL):
        # same contract for a bare adaptive codec (PR-4 behavior)
        print(f"[pipeline] adaptive codec pinned to its current bucket "
              f"R={codec.current_R} (per-step adaptation needs the "
              f"single-program path)", flush=True)
        codec_params = codec.params_for(codec_params)
        codec = codec.current

    params = {
        "embed": {"embed": full["embed"]},
        "blocks": lm_lib.split_stack_for_pipeline(full["stack"]),
        "head": {"final_norm": full["final_norm"], "head": full["head"]},
        "codec": codec_params,
    }
    embed_fn, stage_fn, head_loss_fn = lm_lib.make_pipeline_fns(cfg)
    loss_fn = pipeline_lib.make_pod_pipeline_loss_fn(
        lambda p, x: embed_fn(p, x), stage_fn,
        lambda p, h, y: head_loss_fn(p, h, y), codec, mesh,
        num_microbatches=args.microbatches, async_depth=args.async_depth)

    opt = adamw(args.lr)
    opt_state = opt.init(params)

    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, gn = clip_by_global_norm(grads, 1.0)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state2, loss, gn

    step_fn = (sanitize_lib.checkify_jit(_step) if sanitize_lib
               else jax.jit(_step))
    train_san = sanitize_lib.TrainSanitizer() if sanitize_lib else None

    data = SyntheticTokenDataset(cfg.vocab_size, args.seq, seed=args.seed)
    it = make_batch_iterator(data, args.batch)
    losses = []
    t0 = time.time()
    with jax.set_mesh(mesh):
        for step in range(args.steps):
            b = next(it)
            batch = {"x": b["tokens"], "y": b["labels"]}
            params, opt_state, loss, gn = step_fn(params, opt_state, batch)
            losses.append(loss)   # device value; one sync after the loop
            if train_san is not None:
                train_san.check_step(step, loss=loss, gnorm=gn)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[pipeline] step {step:5d} loss {float(loss):.4f} "  # lint-ok: R3 log-gated (log_every cadence)
                      f"({time.time()-t0:.1f}s)", flush=True)
    losses = [float(l) for l in losses]   # one deferred sync for the run
    wf = transport.split_comm_bytes(codec, mb, directions=1)
    wb = transport.split_comm_bytes(codec, mb) - wf
    print(f"[pipeline] channel: async_depth={args.async_depth}, per-microbatch "
          f"wire fwd {wf:,d} B + bwd {wb:,d} B", flush=True)
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--codec", default="none",
                    help="registry spec, e.g. 'c3sl:R=4|int8', "
                         "'adaptive:c3sl:R=16,min_R=2,target_snr=-6|int8', "
                         "or a per-direction link "
                         "'c3sl:R=8|int8 >> bwd:c3sl:R=4|int8' "
                         "(see repro.codecs / repro.transport)")
    ap.add_argument("--R", type=int, default=4,
                    help="default R for specs that omit it")
    ap.add_argument("--quant", type=int, default=None,
                    help="8 appends the int8 wire stage to the spec")
    ap.add_argument("--unitary", action="store_true")
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--async-depth", type=int, default=1,
                    help="in-flight payload buffers on the pod channel: 1 = "
                         "synchronous (send serializes with the next "
                         "microbatch), 2 = the ppermute overlaps the next "
                         "front pass (one extra bubble step)")
    ap.add_argument("--fault-drop", type=float, default=0.0,
                    help="seeded per-packet drop rate on the cut payload "
                         "(repro.faults.FaultPlan; 0 = clean, and the "
                         "compiled programs are bit-identical to a "
                         "fault-free build)")
    ap.add_argument("--fault-corrupt", type=float, default=0.0,
                    help="seeded per-packet corruption rate on the cut "
                         "payload (corrupt packets are discarded = erased)")
    ap.add_argument("--fault-seed", type=int, default=7,
                    help="FaultPlan seed (the whole chaos run is replayable)")
    ap.add_argument("--fault-mode", choices=["erasure", "retransmit"],
                    default="erasure",
                    help="lossy-step recovery: 'erasure' decodes through "
                         "the renormalized mask (loss degrades SNR, feeds "
                         "the adaptive controller), 'retransmit' NACKs "
                         "until complete and pays the wire bytes")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--sanitize", action="store_true",
                    help="runtime sanitizer tier (repro.analysis.sanitize): "
                         "jax_debug_nans, checkify float checks compiled "
                         "into every step branch, per-step finite checks "
                         "on loss/grad-norm; trades throughput for checks")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    configure_jax()
    if args.pipeline and (args.fault_drop > 0.0 or args.fault_corrupt > 0.0):
        raise SystemExit("fault injection drives the standard loop; the "
                         "pipeline path takes erasure masks through "
                         "make_pod_pipeline_loss_fn(with_erasure=True) "
                         "(see tests/test_faults.py)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"(active {cfg.active_param_count()/1e6:.1f}M)")
    if args.pipeline:
        losses = run_pipeline(args, cfg)
    else:
        losses = run_standard(args, cfg)
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
