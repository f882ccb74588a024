import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init), hence no `from __future__` in this module.
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
against 512 placeholder host devices; record memory/cost/collective stats.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-large-123b \
        --shape train_4k --mesh single [--codec c3sl --R 4] [--pipeline]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # full 40x2 sweep

Results land in benchmarks/results/dryrun/*.json (one file per combo) and
feed EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import codecs, transport
from repro.configs.base import ModelConfig, get_config
from repro.data.pipeline import SHAPES, input_specs
from repro.launch import mesh as mesh_lib
from repro.models import lm as lm_lib
from repro.optim import adamw
from repro.sharding import rules as sh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/results/dryrun")


def shape_adjusted_config(arch: str, shape_name: str) -> ModelConfig | None:
    """Per-shape config variants; None = combination skipped (DESIGN.md)."""
    cfg = get_config(arch)
    if shape_name == "long_500k":
        if cfg.is_encdec:
            return None  # full-attention cross-attn decoder — documented skip
        if not cfg.attention_free:
            # sliding-window variant makes dense/hybrid archs sub-quadratic
            cfg = dataclasses.replace(cfg, sliding_window=4096)
    return cfg


def make_codec(cfg: ModelConfig, shape_name: str, codec_spec: str, R: int,
               quant_bits=None, unitary=False):
    """Build the cut-layer codec (or per-direction ``SplitLink`` from a
    ``... >> bwd:...`` spec) from a registry spec string ("none" = off)."""
    if codec_spec in (None, "", "none"):
        return None, None
    shape = SHAPES[shape_name]
    B = shape["global_batch"]
    if shape["kind"] == "decode":
        D = cfg.d_model
    else:
        # cut-layer feature per sample = (S_total, d_model) flattened
        D = shape["seq_len"] * cfg.d_model
    c = transport.build_link_or_codec(codec_spec, quant_bits=quant_bits,
                                      R=R, D=D, backend="fft",
                                      unitary=unitary)
    c = codecs.clamp_R(c, B if B >= 2 else 1)
    return c, jax.eval_shape(lambda: c.init(jax.random.PRNGKey(0)))


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of collectives in post-SPMD HLO (per device)."""
    sizes = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
             "all-to-all": 0, "collective-permute": 0}
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "c64": 8}
    op_pat = re.compile(
        r"=\s*(\([^)]*\)|\w+\[[\d,]*\][^\s]*)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(")
    shape_pat = re.compile(r"(\w+)\[([\d,]*)\]")
    for m in op_pat.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2)
        for sm in shape_pat.finditer(shapes):
            dtype, dims = sm.group(1), sm.group(2)
            nelem = 1
            for d in dims.split(","):
                if d.strip():
                    nelem *= int(d)
            sizes[op] += nelem * dt_bytes.get(dtype, 4)
    sizes["total"] = sum(sizes.values())
    return sizes


def np_prod_batch_shards(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def roofline_terms(flops, hbm_bytes, coll_bytes, n_chips):
    """Three roofline terms in seconds (cost/collective stats are per-device
    under SPMD, so no extra division by chips)."""
    return {
        "compute_s": flops / mesh_lib.PEAK_FLOPS_BF16,
        "memory_s": hbm_bytes / mesh_lib.HBM_BW,
        "collective_s": coll_bytes / mesh_lib.ICI_BW_PER_LINK,
    }


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    """6*N_active*D tokens processed (training); decode: 2*N_active per token."""
    spec = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if spec["kind"] == "train":
        tokens = spec["global_batch"] * spec["seq_len"]
        return 6.0 * n_active * tokens
    if spec["kind"] == "prefill":
        tokens = spec["global_batch"] * spec["seq_len"]
        return 2.0 * n_active * tokens
    return 2.0 * n_active * spec["global_batch"]  # one token per sequence


def build_train_step(cfg: ModelConfig, codec=None, codec_params=None,
                     num_microbatches: int = 1):
    """Full training step: loss + grads (+ grad-accumulation scan) + AdamW.

    Microbatching bounds peak activation memory: the global batch is split
    into `num_microbatches` chunks processed sequentially with f32 grad
    accumulation (the standard fit-a-big-model configuration)."""
    opt = adamw(1e-4)
    from repro.optim import apply_updates

    def loss_fn(p, mb):
        return lm_lib.lm_loss(p, mb, cfg, codec=codec, codec_params=codec_params)

    def train_step(params, opt_state, batch):
        if num_microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            M = num_microbatches

            def split_mb(x):
                return x.reshape(M, x.shape[0] // M, *x.shape[1:])

            mbs = jax.tree.map(split_mb, batch)

            def body(carry, mb):
                loss_acc, grad_acc = carry
                # barrier: stops XLA hoisting the FSDP param all-gathers out
                # of the microbatch loop (which would materialize the fully
                # gathered stacks at entry and undo the memory saving)
                params_b = jax.lax.optimization_barrier(params)
                l, g = jax.value_and_grad(loss_fn)(params_b, mb)
                grad_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), grad_acc, g)
                return (loss_acc + l, grad_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.array(0.0, jnp.float32), zeros), mbs)
            loss = loss / M
            grads = jax.tree.map(lambda g: g / M, grads)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state2, loss

    return opt, train_step


def _lower_and_compile(cfg, shape_name, mesh, codec, codec_params,
                       param_dtype=jnp.bfloat16, num_microbatches=1):
    spec = SHAPES[shape_name]
    params = lm_lib.abstract_params(cfg, param_dtype)
    param_sh = sh.param_shardings(
        params, mesh, mode="decode" if spec["kind"] == "decode" else "train")
    batch = input_specs(cfg, shape_name)
    batch_sh = sh.batch_shardings(batch, mesh)
    repl = NamedSharding(mesh, P())

    with jax.set_mesh(mesh):
        if spec["kind"] == "train":
            opt, train_step = build_train_step(cfg, codec, codec_params,
                                               num_microbatches)
            opt_state = jax.eval_shape(opt.init, params)
            opt_sh = sh.opt_state_shardings(opt_state, mesh)
            fn = jax.jit(train_step,
                         in_shardings=(param_sh, opt_sh, batch_sh),
                         out_shardings=(param_sh, opt_sh, repl),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params, opt_state, batch)
        elif spec["kind"] == "prefill":
            def prefill(params, batch):
                # serving prefill returns the LAST-token logits (the full
                # (B, S, V) tensor is never materialized for big vocabs)
                logits, _ = lm_lib.lm_forward(params, batch, cfg, remat=False,
                                              last_only=True)
                return logits[:, -1, :]
            bspec = sh.batch_spec(mesh)  # P("data") or P(("pod","data"))
            out_sh = NamedSharding(mesh, sh._guard(
                P(bspec[0], "model"),
                (spec["global_batch"], cfg.vocab_size), mesh))
            fn = jax.jit(prefill, in_shardings=(param_sh, batch_sh),
                         out_shardings=out_sh)
            lowered = fn.lower(params, batch)
        else:  # decode
            cache = lm_lib.abstract_decode_cache(cfg, spec["global_batch"],
                                                 spec["seq_len"], param_dtype)
            cache_sh = sh.cache_shardings(cache, mesh)

            def serve_step(params, cache, tokens, pos):
                return lm_lib.decode_step(params, cache, tokens, pos, cfg,
                                          codec=codec, codec_params=codec_params)

            fn = jax.jit(serve_step,
                         in_shardings=(param_sh, cache_sh, batch_sh["tokens"], repl),
                         out_shardings=(batch_sh["tokens"], cache_sh),
                         donate_argnums=(1,))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = fn.lower(params, cache, batch["tokens"], pos)
        compiled = lowered.compile()
    return lowered, compiled


def dryrun_one(arch: str, shape_name: str, mesh_kind: str, *, codec_kind="none",
               R=4, pipeline=False, quant_bits=None, unitary=False,
               save=True, tag="baseline", param_dtype=jnp.bfloat16,
               cfg_override=None, force_microbatches=None):
    from repro.launch import hloparse
    cfg = cfg_override or shape_adjusted_config(arch, shape_name)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
              "codec": codec_kind, "R": R}
    if cfg is None:
        result["status"] = "skipped"
        result["reason"] = "long_500k unsupported (enc-dec full attention); see DESIGN.md"
        return _save(result) if save else result

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    spec = SHAPES[shape_name]
    t0 = time.time()

    codec, codec_params = make_codec(cfg, shape_name, codec_kind, R,
                                     quant_bits, unitary)

    # auto-tune microbatching until the step fits HBM (train only), stopping
    # at diminishing returns (fixed param/optimizer buffers set a floor)
    HBM_BUDGET = 15 * 2 ** 30  # v5e: 16 GiB minus runtime reserve
    num_microbatches = force_microbatches or 1
    prev_peak = None
    while True:
        lowered, compiled = _lower_and_compile(
            cfg, shape_name, mesh, codec, codec_params, param_dtype,
            num_microbatches)
        m = compiled.memory_analysis()
        peak = ((getattr(m, "argument_size_in_bytes", 0) or 0)
                + (getattr(m, "temp_size_in_bytes", 0) or 0))
        if (force_microbatches or spec["kind"] != "train"
                or peak <= HBM_BUDGET or num_microbatches >= 32):
            break
        if prev_peak is not None and peak > 0.92 * prev_peak:
            break  # plateau: activations no longer dominate
        if spec["global_batch"] // (2 * num_microbatches
                                    * int(np_prod_batch_shards(mesh))) < 1:
            break  # per-device microbatch must stay >= 1
        prev_peak = peak
        num_microbatches *= 2
    result["num_microbatches"] = num_microbatches
    t_lower = time.time() - t0

    mem = compiled.memory_analysis()
    # trip-count-aware HLO analysis (see hloparse; cost_analysis counts
    # while bodies once and is useless for scan-over-layers programs)
    stats = hloparse.analyze(compiled.as_text())
    coll = dict(stats["coll_by_op"])
    coll["total"] = stats["coll_bytes"]
    # mask-aware wire accounting: sparsified (topk) payload bytes MEASURED
    # from the compiled HLO — rows/k/D read off the lowered top-k ops
    # (trip-count aware) instead of trusting the analytic formula; the
    # cross-check against payload_wire_bytes is pinned in
    # tests/test_hloparse.py
    topk_wire = stats["topk_wire_bytes"]
    flops = stats["dot_flops"]
    hbm_bytes = stats["hbm_bytes"]
    mf = model_flops(cfg, shape_name)
    terms = roofline_terms(flops, hbm_bytes, coll["total"], n_chips)
    dominant = max(terms, key=terms.get)
    t_compile = time.time() - t0 - t_lower

    result.update({
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
                          + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": hbm_bytes,
        "collective_bytes_per_device": coll,
        "topk_wire_bytes_hlo": topk_wire,
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops if flops else None,
        "roofline": terms,
        "dominant": dominant,
        "params_global": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    })
    return _save(result) if save else result


def _pod_permute_bytes(hlo: str) -> float:
    """Bytes of collective-permutes whose source->target pairs cross the pod
    boundary (distance 256 on the (2,16,16) mesh) — the SL wire itself, as
    opposed to model-axis resharding permutes.  Microbatch-loop trips are
    already reflected (the permute sits in the scan body, counted per line
    here x its shape; the loop multiplies payload identically across
    variants, so ratios are exact and absolute numbers are per-iteration)."""
    import re as _re
    from repro.launch import hloparse as hp
    total = 0.0
    for ln in hlo.splitlines():
        if "collective-permute" not in ln:
            continue
        pm = _re.search(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}", ln)
        if not pm:
            continue
        pairs = _re.findall(r"\{(\d+),(\d+)\}", pm.group(1))
        if not pairs or abs(int(pairs[0][0]) - int(pairs[0][1])) != 256:
            continue
        m = hp._DEF_RE.match(ln.strip())
        if m:
            _, b = hp._shape_elems_bytes(m.group(2).split(" ")[0])
            total += b
    return total


def pipeline_dryrun(arch: str, *, R: int = 4, quant_bits=None, unitary=False,
                    num_microbatches: int = 4, shape_name: str = "train_4k",
                    tag: str = "pipeline", save: bool = True,
                    codec_kind: str = "c3sl", async_depth: int = 1):
    """Dry-run the 2-stage pod pipeline (paper topology at scale): lower the
    pipelined train loss on the multi-pod mesh and report the inter-pod
    collective-permute bytes — the wire the C3-SL codec compresses.
    ``codec_kind`` may be a ``... >> bwd:...`` link spec (per-direction
    gradient compression); ``async_depth=2`` lowers the double-buffered
    channel schedule."""
    from repro.core import split as split_lib
    from repro.launch import hloparse

    cfg = get_config(arch)
    mesh = mesh_lib.make_production_mesh(multi_pod=True)
    spec = SHAPES[shape_name]
    B, S = spec["global_batch"], spec["seq_len"]
    mb = B // num_microbatches
    D_flat = S * cfg.d_model

    if codec_kind == "none":
        codec = codecs.build("identity", D=D_flat)
        codec_params = {}
    else:
        codec = codecs.clamp_R(
            transport.build_link_or_codec(codec_kind, quant_bits=quant_bits,
                                          R=R, D=D_flat, backend="fft",
                                          unitary=unitary), mb)
        codec_params = jax.eval_shape(lambda: codec.init(jax.random.PRNGKey(0)))

    # f32 params: XLA:CPU's AllReducePromotion pass crashes on the bf16
    # grad all-reduces this program produces (compiler bug); f32 sidesteps
    # it and the codec-compression RATIOS are dtype-independent.
    full = lm_lib.abstract_params(cfg, jnp.float32)
    params = {
        "embed": {"embed": full["embed"]},
        "blocks": jax.eval_shape(lm_lib.split_stack_for_pipeline, full["stack"]),
        "head": {"final_norm": full["final_norm"], "head": full["head"]},
        "codec": codec_params,
    }
    embed_fn, stage_fn, head_loss_fn = lm_lib.make_pipeline_fns(cfg)
    loss_fn = split_lib.make_pod_pipeline_loss_fn(
        embed_fn, stage_fn, head_loss_fn, codec, mesh,
        num_microbatches=num_microbatches, async_depth=async_depth)

    from jax.sharding import NamedSharding
    param_sh = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), params)
    # stage placement: blocks sharded over pod on the leading stage axis
    param_sh["blocks"] = jax.tree.map(
        lambda l: NamedSharding(mesh, sh._guard(
            P("pod", None, None, "model"), l.shape, mesh)),
        params["blocks"])
    batch = {"x": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "y": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    batch_sh = jax.tree.map(  # replicated over pod (both stages read it),
        lambda l: NamedSharding(mesh, sh._guard(  # sharded over data
            P("data", None), l.shape, mesh)), batch)

    def grad_step(params, batch):
        return jax.value_and_grad(loss_fn)(params, batch)

    with jax.set_mesh(mesh):
        lowered = jax.jit(grad_step, in_shardings=(param_sh, batch_sh)).lower(
            params, batch)
        compiled = lowered.compile()

    hlo = compiled.as_text()
    stats = hloparse.analyze(hlo)
    mem = compiled.memory_analysis()
    result = {
        "arch": arch, "shape": shape_name, "mesh": "multi-pipeline",
        "tag": tag, "codec": codec_kind if codec_kind != "none" else "identity",
        # links report the FORWARD channel's R (SplitLink carries no bare R)
        "R": getattr(codec.fwd.current if isinstance(codec, transport.SplitLink)
                     else codec, "R", 1),
        "quant": quant_bits,
        "num_microbatches": num_microbatches, "async_depth": async_depth,
        "status": "ok",
        "collective_bytes_per_device": dict(stats["coll_by_op"],
                                            total=stats["coll_bytes"]),
        "interpod_permute_bytes": _pod_permute_bytes(hlo),
        "topk_wire_bytes_hlo": stats["topk_wire_bytes"],
        "hlo_flops_per_device": stats["dot_flops"],
        "per_device": {"peak_bytes":
                       (getattr(mem, "argument_size_in_bytes", 0) or 0)
                       + (getattr(mem, "temp_size_in_bytes", 0) or 0)},
    }
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        name = f"{arch}_{shape_name}_pipeline_{tag}.json"
        with open(os.path.join(RESULTS_DIR, name), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _save(result):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{result['arch']}_{result['shape']}_{result['mesh']}_{result['tag']}.json"
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--codec", default="none",
                    help="registry spec, e.g. 'c3sl:R=4|int8' (see repro.codecs)")
    ap.add_argument("--R", type=int, default=4)
    ap.add_argument("--quant", type=int, default=None)
    ap.add_argument("--unitary", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    if args.all:
        from repro.configs.archs import ALL_ARCHS
        combos = [(a, s, m) for a in ALL_ARCHS for s in SHAPES
                  for m in ("single", "multi")]
    else:
        combos = [(args.arch, args.shape, args.mesh)]

    failures = 0
    for arch, shape_name, mesh_kind in combos:
        try:
            r = dryrun_one(arch, shape_name, mesh_kind, codec_kind=args.codec,
                           R=args.R, tag=args.tag, quant_bits=args.quant,
                           unitary=args.unitary)
            status = r["status"]
            extra = ""
            if status == "ok":
                pk = r["per_device"]["peak_bytes"]
                extra = (f"peak={pk/2**30:.2f}GiB dom={r['dominant']} "
                         f"compile={r['compile_s']}s")
            print(f"[dryrun] {arch} {shape_name} {mesh_kind}: {status} {extra}",
                  flush=True)
        except Exception:
            failures += 1
            print(f"[dryrun] {arch} {shape_name} {mesh_kind}: FAILED", flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
