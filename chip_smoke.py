"""Chip smoke run: the split-serving stack, once, through its entry points.

    python chip_smoke.py [--seed 0]                # one TPU chip
    python chip_smoke.py --four-chips [--seed 0]   # four TPU chips

One chip runs three phases:

* serve — deepseek-7b at its published widths, depth cut from 30 to 4
  layers so the codec cut splits the stack 2+2, in the engine's own dtype
  (float32).  A paged ``BatchedEngine`` (page size 16, max_len 512, 8
  slots, chunked prefill, link ``c3sl:R=4|int8``) serves two tenants
  behind ``FrontDoorServer`` on loopback, one greedy request at a time.
  Their tokens must equal a direct ``engine.submit`` run of the same
  requests on the same engine, which runs first and so also compiles the
  engine's programs before the front door accepts a connection.
* kernels — the same model with the Pallas paged-attention read and the
  Pallas circular-convolution codec, both compiled for the chip.  One
  decode step's attention outputs and logits must agree with the
  gather/fft path within the tolerances below; greedy-token agreement over
  the served requests is printed.
* train — Adam steps of the paper's VGG-16/CIFAR-10 split at batch 64 with
  ``c3sl:R=4`` on class-conditional synthetic images: losses finite,
  gradients non-zero.

``--four-chips`` runs only the 2-stage pod pipeline on a ``pod=2, data=2``
mesh at deepseek-7b widths: the identity-codec pipeline loss against the
logical forward loss, then a ``c3sl:R=2`` link, and checks in the compiled
program that the collective-permute crosses pods and that neither a
stage's weights nor its batch are replicated onto every chip.

Weights, prompts and images are random, drawn from ``--seed``.  The script
refuses to run without a TPU, exits non-zero if any phase fails, and
prints one JSON object as its last line.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import re
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.codecs import build as build_codec  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.configs.paper import VGG16_CIFAR10  # noqa: E402
from repro.data.pipeline import SyntheticImageDataset  # noqa: E402
from repro.frontdoor.admission import AdmissionController, TenantPolicy  # noqa: E402
from repro.frontdoor.client import FrontDoorClient  # noqa: E402
from repro.frontdoor.server import FrontDoorServer  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.runtime import configure_jax  # noqa: E402
from repro.models import attention as attn_lib  # noqa: E402
from repro.models import convnets  # noqa: E402
from repro.models import lm as lm_lib  # noqa: E402
from repro.models.layers import softmax_cross_entropy  # noqa: E402
from repro.models.paging import PagedLayout, gather_pages  # noqa: E402
from repro.optim import adam, apply_updates  # noqa: E402
from repro.serving.engine import BatchedEngine, Request  # noqa: E402
from repro.transport import pipeline as pipeline_lib  # noqa: E402
from repro.transport.split import apply_codec  # noqa: E402

SERVE_LAYERS = 4            # 30 published; 2 + 2 around the codec cut
SLOTS, MAX_LEN, PAGE = 8, 512, 16
LINK = "c3sl:R=4|int8"
KERNEL_LINK = "c3sl:R=4,backend=pallas|int8"
TENANTS = ("tenant-a", "tenant-b")
REQUESTS_PER_TENANT = 2
PROMPT_LEN = (64, 128)      # inclusive range of prompt lengths
MAX_NEW = 32

# Kernel-vs-reference tolerances, as a fraction of the reference's largest
# magnitude.  Either side may run its float32 matmuls as bfloat16 passes
# (XLA's default precision on TPU; 2**-8 relative per product), and the
# logits also carry the int8 wire stage: a codec rounding difference moves
# a payload element by one quantum, 1/127 of its row's largest value.
ATTN_RTOL = 2e-2
LOGITS_RTOL = 5e-2

TRAIN_STEPS = 3

# Depth from memory_analysis() of the loss-and-grad program on a described
# v5e:2x2: 10.6 GiB per chip at 2 layers, 14.6 GiB at 4, 21.0 GiB at 8.
FOUR_CHIP_LAYERS = 2
PIPE_BATCH, PIPE_SEQ, PIPE_MICROBATCHES = 8, 128, 2
PIPE_LOSS_ATOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's trace, lower and backend-compile durations, so each phase
    can report the seconds it spent compiling."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def report(phase: str, clock: CompileClock, t0: float, c0: float) -> None:
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    log(f"{phase}: wall {time.time() - t0:.1f}s, compile "
        f"{clock.seconds - c0:.1f}s, peak_bytes_in_use "
        f"{', '.join(str(p) for p in peaks)}")


def deepseek(num_layers: int):
    cfg = dataclasses.replace(get_config("deepseek-7b"), num_layers=num_layers)
    log(f"deepseek-7b: d_model {cfg.d_model}, heads {cfg.num_heads}/"
        f"{cfg.num_kv_heads}, head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}; depth cut 30 -> {num_layers} layers")
    return cfg


def make_prompts(seed: int, vocab: int) -> dict[str, list[list[int]]]:
    rng = np.random.RandomState(seed)
    lo, hi = PROMPT_LEN
    return {t: [[int(x) for x in rng.randint(1, vocab, rng.randint(lo, hi + 1))]
                for _ in range(REQUESTS_PER_TENANT)] for t in TENANTS}


def make_engine(params, cfg, seed: int, *, link: str, kv_read: str):
    with warnings.catch_warnings():
        # the kernel read names the reads it leaves on gather (prefill)
        warnings.simplefilter("ignore")
        return BatchedEngine(params, cfg, num_slots=SLOTS, max_len=MAX_LEN,
                             codec=link, greedy=True, seed=seed,
                             kv_layout="paged", page_size=PAGE,
                             kv_read=kv_read)


def run_direct(eng, prompts) -> dict[str, list[list[int]]]:
    """Each request alone through ``engine.submit``/``run`` — the schedule
    the sequential front-door tenants produce."""
    out, uid = {}, 10_000
    for tenant, reqs in prompts.items():
        out[tenant] = []
        for p in reqs:
            eng.submit(Request(uid=uid, prompt=list(p), max_new_tokens=MAX_NEW))
            done = eng.run()
            out[tenant].append(done[-1].out)
            eng.finished.clear()
            uid += 1
    return out


async def run_frontdoor(eng, prompts, link: str) -> dict[str, list[list[int]]]:
    server = FrontDoorServer(eng, admission=AdmissionController(
        max_queue_depth=16, default_policy=TenantPolicy(max_inflight=4)))
    host, port = await server.start()
    log(f"front door on {host}:{port}")
    out = {}
    try:
        for tenant, reqs in prompts.items():
            client = await FrontDoorClient.open(host, port, tenant=tenant,
                                                codec=link)
            try:
                out[tenant] = [(await client.generate(p, max_new=MAX_NEW))
                               ["tokens"] for p in reqs]
            finally:
                await client.close()
    finally:
        await server.stop()
    if server.tick_error is not None:
        raise RuntimeError(f"front door tick loop failed: {server.tick_error!r}")
    return out


def serve_phase(cfg, params, prompts, seed: int):
    eng = make_engine(params, cfg, seed, link=LINK, kv_read="gather")
    direct = run_direct(eng, prompts)
    served = asyncio.run(run_frontdoor(eng, prompts, LINK))
    if served != direct:
        for t in TENANTS:
            log(f"MISMATCH {t}: direct {direct[t]} front door {served[t]}")
        raise AssertionError("front-door tokens differ from the direct engine run")
    outs = [o for t in TENANTS for o in served[t]]
    log(f"serve: {len(outs)} requests from {len(TENANTS)} tenants through "
        f"the front door equal the direct engine run "
        f"({sum(map(len, outs))} tokens); wire fwd "
        f"{eng.stats['wire_bytes_fwd']} B")
    return direct


def random_paged_state(params, cfg, seed: int):
    """A full paged cache filled with random K/V, shuffled page tables and
    staggered positions: one realistic decode-step input."""
    rng = np.random.RandomState(seed)
    pps = MAX_LEN // PAGE
    layout = PagedLayout(PAGE, MAX_LEN, SLOTS * pps, 0, 0)
    cache = lm_lib.init_decode_cache(params, cfg, SLOTS, MAX_LEN, paged=layout)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 len(jax.tree.leaves(cache["stack"]))))
    cache["stack"] = jax.tree.map(
        lambda x: jax.random.normal(next(keys), x.shape, x.dtype),
        cache["stack"])
    cache["pages"] = jnp.asarray(
        rng.permutation(SLOTS * pps).astype(np.int32).reshape(SLOTS, pps))
    pos = jnp.asarray(rng.randint(MAX_LEN // 4, MAX_LEN, SLOTS), jnp.int32)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, (SLOTS, 1)), jnp.int32)
    return layout, cache, pos, tokens


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.abs(got - want).max() / np.abs(want).max())


def kernel_phase(cfg, params, prompts, reference, seed: int) -> dict:
    eng = make_engine(params, cfg, seed, link=KERNEL_LINK, kv_read="kernel")
    modes = (eng.stats["kv_read_execution_mode"],
             eng.stats["codec_execution_mode"])
    log(f"kernels: kv_read {modes[0]}, codec {modes[1]}")

    layout, cache, pos, tokens = random_paged_state(params, cfg, seed)
    # attention: one layer's pools, read in-kernel and through gather_pages
    pools = jax.tree.map(lambda x: x[0], cache["stack"]["l0_0_attn"])
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (SLOTS, 1, cfg.num_heads, cfg.head_dim))
    att_k = jax.jit(lambda q, c, t, p: kops.paged_attention_decode(  # lint-ok: R1 runs once
        q, c, t, p, length=MAX_LEN))(q, pools, cache["pages"], pos)
    att_g = jax.jit(lambda q, c, t, p: attn_lib.sdpa_decode(  # lint-ok: R1 runs once
        q, {n: gather_pages(v, t, MAX_LEN) for n, v in c.items()}, p,
        MAX_LEN))(q, pools, cache["pages"], pos)
    attn_err = rel_err(att_k, att_g)

    def step(link, kv_read):
        codec = build_codec(link, D=cfg.d_model)
        cparams = codec.init(jax.random.PRNGKey(seed))
        fn = jax.jit(lambda p, c, tok, ps: lm_lib.decode_step(
            p, c, tok, ps, cfg, codec=codec, codec_params=cparams,
            paged=layout, kv_read=kv_read)[0])
        return fn(params, cache, tokens, pos)

    logits_g = step(LINK, "gather")
    logits_k = step(KERNEL_LINK, "kernel")
    logit_err = rel_err(logits_k, logits_g)
    log(f"kernels: attention max|diff|/max|ref| {attn_err:.3e} (tol {ATTN_RTOL}); "
        f"decode-step logits {logit_err:.3e} (tol {LOGITS_RTOL})")

    got = run_direct(eng, prompts)
    same = total = 0
    for t in TENANTS:
        for a, b in zip(got[t], reference[t]):
            same += sum(x == y for x, y in zip(a, b))
            total += max(len(a), len(b))
    log(f"kernels: greedy tokens agreeing with the gather/fft engine "
        f"{same}/{total}")
    return {"modes": modes, "attn_err": attn_err, "logit_err": logit_err}


def train_phase(seed: int) -> None:
    pc = VGG16_CIFAR10
    codec = build_codec("c3sl:R=4", D=pc.D)
    cparams = codec.init(jax.random.PRNGKey(seed))
    params = convnets.init_vgg16(jax.random.PRNGKey(seed), n_classes=pc.n_classes)
    opt = adam(pc.lr)
    opt_state = opt.init(params)
    data = SyntheticImageDataset(n_classes=pc.n_classes, seed=seed)

    def loss_fn(p, batch):
        z = convnets.vgg16_front(p, batch["x"])
        logits = convnets.vgg16_back(p, apply_codec(codec, cparams, z))
        logp = jax.nn.log_softmax(logits)
        return -logp[jnp.arange(batch["y"].shape[0]), batch["y"]].mean()

    @jax.jit
    def step(p, s, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        updates, s = opt.update(grads, s, p)
        return apply_updates(p, updates), s, loss, gnorm

    losses, gnorms = [], []
    for i in range(TRAIN_STEPS):
        params, opt_state, loss, gnorm = step(params, opt_state,
                                              data.batch(pc.batch_size, i))
        losses.append(loss)
        gnorms.append(gnorm)
    losses, gnorms = [float(x) for x in losses], [float(x) for x in gnorms]
    log(f"train: {pc.name} split at {pc.cut_shape} (D={pc.D}), batch "
        f"{pc.batch_size}, c3sl:R=4; losses {losses}, grad norms {gnorms}")
    if not all(np.isfinite(losses)) or not all(g > 0 and np.isfinite(g)
                                               for g in gnorms):
        raise AssertionError("training produced a non-finite loss or a zero "
                             "gradient")


# ---------------------------------------------------------------------------
# four chips: the 2-stage pod pipeline
# ---------------------------------------------------------------------------

def pipeline_mesh(devices):
    from jax.sharding import AxisType, Mesh
    return Mesh(np.asarray(devices).reshape(2, 2, 1), ("pod", "data", "model"),
                axis_types=(AxisType.Auto,) * 3)


def pipeline_params(cfg, key):
    full = lm_lib.init_lm_params(key, cfg)
    return {"embed": {"embed": full["embed"]},
            "blocks": lm_lib.split_stack_for_pipeline(full["stack"]),
            "head": {"final_norm": full["final_norm"], "head": full["head"]}}


def pipeline_program(cfg, mesh, link: str):
    """(jitted loss-and-grad, codec params, param shardings, batch sharding)
    for one cut link on the pod pipeline."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    codec = build_codec(link, D=PIPE_SEQ * cfg.d_model)
    cparams = codec.init(jax.random.PRNGKey(7)) if link != "identity" else {}
    embed_fn, stage_fn, head_loss_fn = lm_lib.make_pipeline_fns(cfg)
    loss_fn = pipeline_lib.make_pod_pipeline_loss_fn(
        embed_fn, stage_fn, head_loss_fn, codec, mesh,
        num_microbatches=PIPE_MICROBATCHES)
    shapes = jax.eval_shape(lambda k: pipeline_params(cfg, k),
                            jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    shard = {k: jax.tree.map(lambda _: NamedSharding(mesh, P("pod"))
                             if k == "blocks" else rep, v)
             for k, v in shapes.items()}
    fn = jax.jit(jax.value_and_grad(
        lambda p, c, b: loss_fn(dict(p, codec=c), b)))
    return fn, cparams, shard, NamedSharding(mesh, P("data"))


def check_pipeline_hlo(text: str, cfg) -> str:
    """The pod hop must cross pods, and each chip must hold one stage's
    weights and one data shard of the batch (not a replica of either)."""
    pairs = [tuple(map(int, p)) for m in re.finditer(
        r"collective-permute(?:-start)?\(.*?source_target_pairs=\{(.*?)\}\}",
        text) for p in re.findall(r"(\d+),(\d+)", m.group(1))]
    if not pairs or any(s // 2 == t // 2 for s, t in pairs):
        raise AssertionError(f"collective-permute pairs {pairs} do not all "
                             "cross pods (devices 0,1 | 2,3)")
    L, d, f = cfg.num_layers, cfg.d_model, cfg.d_ff
    if f"[{L},{d},{f}]" in text or f"[2,{L // 2},{d},{f}]" in text:
        raise AssertionError("both stages' MLP weights sit on one chip")
    tokens = PIPE_BATCH // PIPE_MICROBATCHES * PIPE_SEQ
    rows = {int(np.prod([int(x) for x in m.group(1).split(",")]))
            for m in re.finditer(rf"\[([\d,]+),{f}\]", text)}
    if tokens in rows or tokens // 2 not in rows:
        raise AssertionError(f"MLP activations with {sorted(rows)} token rows: "
                             f"expected {tokens // 2} per chip (data-sharded), "
                             f"never {tokens}")
    return f"pairs {sorted(set(pairs))}, {tokens // 2} token rows per chip"


def four_chip_phase(seed: int) -> None:
    cfg = deepseek(FOUR_CHIP_LAYERS)
    mesh = pipeline_mesh(jax.devices())
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (PIPE_BATCH, PIPE_SEQ), 0, cfg.vocab_size)
    results, params = {}, None
    with jax.set_mesh(mesh):
        for link in ("identity", "c3sl:R=2"):
            fn, cparams, shard, bshard = pipeline_program(cfg, mesh, link)
            if params is None:
                params = jax.jit(lambda k: pipeline_params(cfg, k),  # lint-ok: R1 runs once
                                 out_shardings=shard)(jax.random.PRNGKey(seed))
                batch = jax.device_put({"x": tokens, "y": tokens}, bshard)
            compiled = fn.lower(params, cparams, batch).compile()
            mem = compiled.memory_analysis()
            log(f"pipeline {link}: per-chip arguments "
                f"{mem.argument_size_in_bytes}, temporaries "
                f"{mem.temp_size_in_bytes}, outputs {mem.output_size_in_bytes} B")
            log(f"pipeline {link}: {check_pipeline_hlo(compiled.as_text(), cfg)}")
            loss, grads = compiled(params, cparams, batch)
            gabs = float(sum(jnp.sum(jnp.abs(g)) for g in jax.tree.leaves(grads)))
            del grads
            results[link] = (float(loss), gabs)
            log(f"pipeline {link}: loss {float(loss):.6f}, sum|grad| {gabs:.6e}")

        def logical_loss(p):
            full = {"embed": p["embed"]["embed"],
                    "stack": jax.tree.map(
                        lambda a: a.reshape((-1,) + a.shape[2:]), p["blocks"]),
                    **p["head"]}
            out, _ = lm_lib.lm_forward(full, {"tokens": tokens}, cfg, remat=False)
            return softmax_cross_entropy(out, tokens)

        ref = float(jax.jit(logical_loss)(params))
    diff = abs(results["identity"][0] - ref)
    log(f"pipeline: identity loss {results['identity'][0]:.6f} vs logical "
        f"lm_forward loss {ref:.6f}: |diff| {diff:.3e} (tol {PIPE_LOSS_ATOL})")
    if not diff < PIPE_LOSS_ATOL:
        raise AssertionError("identity-codec pipeline loss differs from the "
                             "logical loss")
    loss, gabs = results["c3sl:R=2"]
    if not (np.isfinite(loss) and np.isfinite(gabs) and gabs > 0):
        raise AssertionError("c3sl:R=2 pipeline loss non-finite or gradients zero")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2-stage pod pipeline on four chips")
    args = ap.parse_args()
    configure_jax()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform!r} devices; refusing to run")
        return 1
    count = len(jax.devices())
    log(f"device_kind {dev.device_kind!r}, count {count}")
    clock = CompileClock()
    if args.four_chips:
        if count != 4:
            log(f"--four-chips needs 4 devices, found {count}")
            return 1
        t0, c0 = time.time(), clock.seconds
        four_chip_phase(args.seed)
        report("pipeline", clock, t0, c0)
    else:
        cfg = deepseek(SERVE_LAYERS)
        t0, c0 = time.time(), clock.seconds
        params = lm_lib.init_lm_params(jax.random.PRNGKey(args.seed), cfg)
        prompts = make_prompts(args.seed, cfg.vocab_size)
        reference = serve_phase(cfg, params, prompts, args.seed)
        report("serve", clock, t0, c0)
        t0, c0 = time.time(), clock.seconds
        res = kernel_phase(cfg, params, prompts, reference, args.seed)
        report("kernels", clock, t0, c0)
        if res["modes"] != ("pallas-compiled", "pallas-compiled"):
            raise AssertionError(f"kernels not compiled: {res['modes']}")
        if not (res["attn_err"] <= ATTN_RTOL and res["logit_err"] <= LOGITS_RTOL):
            raise AssertionError("kernel path outside its stated tolerance")
        del params
        t0, c0 = time.time(), clock.seconds
        train_phase(args.seed)
        report("train", clock, t0, c0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
