"""Batched serving driver: lockstep decode loop, or the full
continuous-batching engine with chunked prefill (--engine).

Runs for real on CPU with reduced configs; demonstrates the C3-SL serving
integration (cut-layer features compressed batch-wise across the decode
batch).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --batch 8 --steps 32 --codec "c3sl:R=4"

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --reduced \
        --engine --requests 16 --prompt-len 64 --max-new 16 \
        --chunk-size 16 --codec "c3sl:R=4|int8"

    # multi-tenant networked front door (see src/repro/frontdoor/README.md)
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --reduced \
        --frontdoor --port 8787 --kv-layout paged --preemption \
        --codec "adaptive:c3sl:R=4,min_R=2|int8"
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import codecs, transport
from repro.configs.base import get_config, reduced
from repro.launch.runtime import configure_jax
from repro.models import lm as lm_lib


def _serving_codec(spec: str, D: int, R: int, batch: int):
    """Build the serving-side codec from a spec.  Per-direction link specs
    (``... >> bwd:...``) keep the LINK: the engine serves the forward
    channel — no gradient crosses the cut at inference, so the backward
    codec is accounted as wire_bytes_bwd == 0 — and a ``draft:`` segment
    becomes the speculative feedback channel (auto-enables spec decode)."""
    if transport.is_link_spec(spec):
        link = transport.build_link(spec, D=D, R=R).with_max_R(batch)
        print(f"[serve] link spec {link.spec()!r}: forward channel serves "
              f"(no gradient crosses the cut at inference)"
              + ("; draft channel feeds speculative decode"
                 if link.draft is not None else ""), flush=True)
        return link
    return codecs.clamp_R(codecs.build(spec, D=D, R=R), batch)


def _run_engine(cfg, params, args):
    """Continuous batching: chunked prefill + device-resident stepping."""
    from repro.serving.engine import Request
    eng = _build_engine(cfg, params, args)
    rng = jax.random.PRNGKey(args.seed + 1)
    prompts = jax.random.randint(rng, (args.requests, args.prompt_len), 0,
                                 cfg.vocab_size)
    for u, p in enumerate(prompts.tolist()):
        eng.submit(Request(uid=u, prompt=p, max_new_tokens=args.max_new))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    gen = sum(len(r.out) for r in done)
    total = gen + args.requests * args.prompt_len
    print(f"arch={cfg.name} engine "
          f"slots={args.batch} chunk={eng.chunk_size} sync={eng.sync_every} "
          f"kv={args.kv_layout} interleave={eng.interleave} "
          f"codec={eng.codec.spec() if eng.codec is not None else 'none'}")
    if eng.codec is not None:
        line = (f"cut-layer wire: fwd {eng.stats['wire_bytes_fwd']:,d} B + "
                f"bwd {eng.stats['wire_bytes_bwd']:,d} B "
                f"over {eng.stats['decode_steps']} decode steps + "
                f"{eng.stats['prefill_chunks']} prefill chunks")
        if eng.r_served:
            hist = dict(sorted(eng.r_served.items()))
            line += f"; served R schedule {hist} (decode steps + chunks)"
        print(line)
    if eng.spec_cfg is not None:
        s = eng.stats
        tried = s["spec_accepted"] + s["spec_rejected"]
        wpt = eng.wire_per_token()
        print(f"speculative: k={eng._k_ctl.current_k} "
              f"head={eng.spec_cfg.draft_head} "
              f"draft={eng.draft_codec.spec() if eng.draft_codec else 'raw'} "
              f"rounds={s['spec_rounds']} accepted={s['spec_accepted']} "
              f"rejected={s['spec_rejected']} rollbacks={s['spec_rollbacks']} "
              f"(acceptance {s['spec_accepted'] / max(tried, 1):.2f}); "
              f"wire {wpt['wire_bytes_per_token']:.1f} B/token "
              f"(fwd {wpt['wire_bytes_fwd']:,d} + "
              f"draft {wpt['wire_bytes_draft']:,d} B)")
    if eng.paged is not None:
        print(f"paged pool: {eng.paged.num_pages} pages x "
              f"{eng.paged.page_size} positions "
              f"(vs {args.batch * args.cache_len} contiguous positions); "
              f"cache bytes {eng.cache_bytes}")
    ttfts = [r.t_first - r.t_submit for r in done if r.t_first is not None]
    print(f"{len(done)} requests ({args.requests * args.prompt_len} prompt + "
          f"{gen} generated tokens) in {dt:.2f}s ({total / dt:.1f} tok/s); "
          f"mean TTFT {sum(ttfts) / max(len(ttfts), 1) * 1e3:.1f}ms; "
          f"dispatches {eng.stats['dispatches']}")
    print("sample output:", done[0].out[:16])


def _spec_config(args):
    """SpecConfig from the --draft-* flags; None when none were given (a
    --codec link spec with a draft: segment still auto-enables in the
    engine with defaults)."""
    from repro.serving.spec import SpecConfig
    if (args.draft_k is None and args.draft_spec is None
            and args.draft_head is None and not args.draft_adaptive):
        return None
    kw = {}
    if args.draft_k is not None:
        kw["k"] = args.draft_k
    if args.draft_spec is not None and args.draft_spec != "none":
        kw["draft"] = args.draft_spec
    if args.draft_head is not None:
        kw["draft_head"] = args.draft_head
    if args.draft_adaptive:
        kw["adaptive"] = True
    return SpecConfig(**kw)


def _build_engine(cfg, params, args):
    from repro.serving.engine import BatchedEngine
    codec = None
    if args.codec != "none":
        codec = _serving_codec(args.codec, cfg.d_model, args.R, args.batch)
    spec_decode = _spec_config(args)
    if spec_decode is not None and not args.greedy:
        raise SystemExit("--draft-* speculative decoding needs --greedy "
                         "(greedy verification is the bit-identity "
                         "guarantee)")
    eng = BatchedEngine(params, cfg, num_slots=args.batch,
                        max_len=args.cache_len, codec=codec,
                        codec_params=(codec.init(jax.random.PRNGKey(7))
                                      if codec is not None else None),
                        greedy=args.greedy, seed=args.seed,
                        chunk_size=args.chunk_size, sync_every=args.sync_every,
                        kv_layout=args.kv_layout, page_size=args.page_size,
                        num_pages=args.num_pages, interleave=args.interleave,
                        preemption=args.preemption, spec_decode=spec_decode)
    if args.pin_R is not None:
        if not isinstance(eng.codec, codecs.AdaptiveC3SL):
            raise SystemExit("--pin-R needs an 'adaptive:...' --codec spec")
        eng.codec.pin(args.pin_R)
    if getattr(args, "sanitize", False):
        from repro.analysis.sanitize import EngineSanitizer, enable_debug_nans
        enable_debug_nans()
        eng.attach_sanitizer(EngineSanitizer(eng))
        print("[sanitize] debug_nans + per-tick engine invariant checks "
              "armed (pool accounting, slot hygiene, live-slot cut "
              "zeroing)", flush=True)
    return eng


def _run_frontdoor(cfg, params, args):
    """Serve the engine over the multi-tenant front door (TCP loopback by
    default) until interrupted.  Clients connect with
    ``repro.frontdoor.FrontDoorClient`` or anything speaking the frame
    protocol in ``src/repro/frontdoor/README.md``."""
    import asyncio

    from repro.frontdoor import (AdmissionController, FrontDoorServer,
                                 TenantPolicy)
    eng = _build_engine(cfg, params, args)
    server = FrontDoorServer(
        eng, host=args.host, port=args.port,
        admission=AdmissionController(
            max_queue_depth=args.max_queue_depth,
            default_policy=TenantPolicy(max_inflight=args.max_inflight)))

    async def serve():
        detector = None
        if getattr(args, "sanitize", False):
            from repro.analysis.sanitize import SlowCallbackDetector
            detector = SlowCallbackDetector().install()
        host, port = await server.start()
        spec = eng.codec.spec() if eng.codec is not None else "none"
        print(f"[serve] front door on {host}:{port} arch={cfg.name} "
              f"slots={args.batch} kv={args.kv_layout} codec={spec} "
              f"preemption={args.preemption} (ctrl-c to stop)", flush=True)
        try:
            await asyncio.Event().wait()
        finally:
            if detector is not None:
                await detector.stop()
                print(f"[sanitize] {detector.report()}", flush=True)
            await server.stop(drain=False)

    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        pass
    print(f"[serve] front door stopped; engine stats: "
          f"dispatches={eng.stats['dispatches']} "
          f"evictions={eng.stats['evictions']} "
          f"wire fwd {eng.stats['wire_bytes_fwd']:,d} B")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--codec", default="none",
                    help="registry spec, e.g. 'c3sl:R=4|int8', "
                         "'adaptive:c3sl:R=8,min_R=2|int8', or a link spec "
                         "'c3sl:R=4|int8 >> bwd:c3sl:R=2' (serving uses the "
                         "forward channel; see repro.transport)")
    ap.add_argument("--R", type=int, default=4,
                    help="default R for specs that omit it")
    ap.add_argument("--pin-R", type=int, default=None,
                    help="pin an adaptive codec's schedule to one bucket "
                         "(serving has no in-graph SNR probe; R is driven "
                         "externally via engine.observe_snr or pinned)")
    ap.add_argument("--quant-kv", action="store_true",
                    help="int8 KV cache (2x less cache HBM)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine (chunked prefill + "
                         "device-resident slot state) instead of the "
                         "lockstep decode loop")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--chunk-size", type=int, default=16)
    ap.add_argument("--sync-every", type=int, default=8)
    ap.add_argument("--kv-layout", choices=["contiguous", "paged"],
                    default="contiguous",
                    help="'paged' = shared page pool + per-slot page tables "
                         "(short requests stop reserving max_len positions)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache positions per page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical pages in the pool (default: fully "
                         "provisioned = slots * ceil(max_len/page_size); "
                         "smaller pools oversubscribe and queue admissions)")
    ap.add_argument("--interleave", type=int, default=0,
                    help="decode steps interleaved after each prefill chunk "
                         "(0 = prefill admitted prompts to completion; the "
                         "TTFT vs inter-token-latency knob)")
    ap.add_argument("--draft-k", type=int, default=None,
                    help="speculative decoding: draft tokens per verify "
                         "round (k positions advance per round trip; "
                         "engine/frontdoor modes, needs --greedy)")
    ap.add_argument("--draft-spec", default=None,
                    help="draft feedback channel codec spec, e.g. "
                         "'c3sl:R=8|int8' ('none' = raw f32 feedback); "
                         "overrides a --codec link spec's 'draft:' segment")
    ap.add_argument("--draft-head", choices=["tied", "copy"], default=None,
                    help="client-side draft proposer: 'tied' (tied-embedding "
                         "head over the fed-back cut feature) or 'copy' "
                         "(repeat last token, zero feedback bytes)")
    ap.add_argument("--draft-adaptive", action="store_true",
                    help="adapt k from the measured acceptance rate "
                         "(EMA deadband over the {1,2,4,8} ladder)")
    ap.add_argument("--preemption", action="store_true",
                    help="evict lower-priority slots (pages freed, request "
                         "re-queued for re-prefill) instead of FIFO-blocking "
                         "when the queue head cannot be admitted "
                         "(chunked prefill only)")
    ap.add_argument("--frontdoor", action="store_true",
                    help="serve the engine over the multi-tenant TCP front "
                         "door (repro.frontdoor) instead of running a local "
                         "request batch")
    ap.add_argument("--host", default="127.0.0.1",
                    help="front door bind address")
    ap.add_argument("--port", type=int, default=8787,
                    help="front door port (0 = ephemeral)")
    ap.add_argument("--max-inflight", type=int, default=8,
                    help="per-tenant in-flight request cap (front door)")
    ap.add_argument("--max-queue-depth", type=int, default=64,
                    help="server-wide backlog cap before BUSY shedding "
                         "(front door)")
    ap.add_argument("--sanitize", action="store_true",
                    help="runtime sanitizer tier (repro.analysis.sanitize): "
                         "jax_debug_nans + per-tick engine invariant checks "
                         "(--engine/--frontdoor paths; an invariant trip "
                         "raises out of the serving loop) and event-loop "
                         "stall diagnostics on the front door")
    args = ap.parse_args()
    configure_jax()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.quant_kv:
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    rng = jax.random.PRNGKey(args.seed)
    params = lm_lib.init_lm_params(rng, cfg)

    if args.frontdoor:
        _run_frontdoor(cfg, params, args)
        return
    if args.engine:
        _run_engine(cfg, params, args)
        return

    codec = codec_params = None
    if args.codec != "none":
        codec = _serving_codec(args.codec, cfg.d_model, args.R, args.batch)
        codec_params = codec.init(jax.random.PRNGKey(7))
        if isinstance(codec, transport.SplitLink):
            # lockstep loop serves the forward channel (same fwd params —
            # link.init feeds every channel the same rng)
            codec_params = codec.fwd_params(codec_params)
            codec = codec.fwd.codec
    adaptive = isinstance(codec, codecs.AdaptiveC3SL)
    if args.pin_R is not None:
        if not adaptive:
            raise SystemExit("--pin-R needs an 'adaptive:...' --codec spec")
        codec.pin(args.pin_R)

    fe = None
    if cfg.frontend:
        fe = jax.random.normal(rng, (args.batch, cfg.frontend_seq, cfg.frontend_dim))
    cache = lm_lib.init_decode_cache(params, cfg, args.batch, args.cache_len,
                                     frontend_emb=fe)

    def make_step(step_codec, step_codec_params):
        # one compiled branch per (bucket) codec; the Adaptive-R wrapper
        # itself must never be closed over by jit (host-side switching)
        @jax.jit
        def step(params, cache, tokens, pos, key):
            logits, cache = lm_lib.decode_step(params, cache, tokens, pos, cfg,
                                               codec=step_codec,
                                               codec_params=step_codec_params)
            if args.greedy:
                nxt = jnp.argmax(logits[:, -1], axis=-1)
            else:
                nxt = jax.random.categorical(key, logits[:, -1], axis=-1)
            return nxt[:, None].astype(jnp.int32), cache

        return step

    step_fns = codecs.build_program_table(codec, codec_params, make_step)

    tokens = jax.random.randint(rng, (args.batch, 1), 0, cfg.vocab_size)
    t0 = time.time()
    outs = [tokens]
    wire_total = 0
    for t in range(args.steps):
        rng, key = jax.random.split(rng)
        R = codecs.program_key(codec)
        tokens, cache = step_fns[R](params, cache, tokens, jnp.int32(t), key)
        if codec is not None:
            step_codec = codec.buckets[R] if adaptive else codec
            wire_total += codecs.payload_wire_bytes(
                step_codec, step_codec.payload_shape(args.batch))
        outs.append(tokens)
    dt = time.time() - t0
    seq = jnp.concatenate(outs, axis=1)
    print(f"arch={cfg.name} batch={args.batch} steps={args.steps} "
          f"codec={codec.spec() if codec is not None else 'none'} "
          f"R={getattr(codec, 'R', 1)}")
    print(f"decoded {args.steps} tokens/seq in {dt:.2f}s "
          f"({args.batch*args.steps/dt:.1f} tok/s total)")
    print("sample token ids:", seq[0, :16].tolist())
    if codec is not None:
        base = args.steps * args.batch * cfg.d_model * 4
        print(f"cut-layer wire bytes: {wire_total} over {args.steps} steps "
              f"vs vanilla {base} ({base/max(wire_total, 1):.1f}x compression)")


if __name__ == "__main__":
    main()
