"""Double-buffered pod-pipeline channel tests.

Pins the ``async_depth`` staleness semantics on a deterministic two-pod
simulated mesh: depth=1 IS the synchronous schedule; depth=2 consumes
microbatch t's payload at step t+2 (one-slot skew) — pairing is preserved,
so loss AND grads are bit-identical to the synchronous schedule while the
scan grows exactly depth-1 bubble steps (pinned through the compiled HLO's
trip-count-aware FLOP totals).  Runs in subprocesses (XLA device count
locks at first jax init)."""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 2) -> dict:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


COMMON = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro import transport
    from repro.codecs import build
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_host_mesh(data=1, model=1, pod=2)
    B, S, E, M = 16, 4, 6, 4
    rng = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    embed_p = jax.random.normal(k1, (7, E)) * 0.3
    blocks = jax.random.normal(k2, (2, 1, E, E)) * 0.2
    head_p = jax.random.normal(k3, (E,)) * 0.5

    def embed_fn(p, x):  return p[x]
    def stage_fn(bl, h): return jnp.tanh(h @ bl[0])
    def head_loss_fn(hp, h, y): return jnp.mean(((h @ hp) - y) ** 2)

    x = jax.random.randint(k4, (B, S), 0, 7)
    y = jax.random.normal(jax.random.PRNGKey(9), (B, S))
    D = S * E
    batch = {"x": x, "y": y}

    def run(depth, codec, params):
        lf = transport.make_pod_pipeline_loss_fn(
            embed_fn, stage_fn, head_loss_fn, codec, mesh,
            num_microbatches=M, async_depth=depth)
        with jax.set_mesh(mesh):
            return jax.jit(jax.value_and_grad(lf))(params, batch)

    def leaves_equal(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
""")


def test_depth1_and_depth2_bit_identical_c3sl():
    """The skew delays payload consumption but never mis-pairs microbatch
    payloads with labels, so loss and gradients are bit-identical across
    depths — the staleness-semantics pin, with the paper codec on the
    channel."""
    r = run_py(COMMON + textwrap.dedent("""
        codec = build("c3sl:R=2", D=D)
        params = {"embed": embed_p, "blocks": blocks, "head": head_p,
                  "codec": codec.init(jax.random.PRNGKey(7))}
        l1, g1 = run(1, codec, params)
        l2, g2 = run(2, codec, params)
        l3, g3 = run(3, codec, params)
        print(json.dumps({
            "l1": float(l1), "l2": float(l2), "l3": float(l3),
            "g12": bool(leaves_equal(g1, g2)),
            "g13": bool(leaves_equal(g1, g3)),
        }))
    """))
    assert r["l1"] == r["l2"] == r["l3"], r
    assert r["g12"] and r["g13"], r


def test_depth2_matches_per_microbatch_reference():
    """Deterministic two-pod regression: the skewed schedule's loss equals
    the hand-rolled per-microbatch reference (each microbatch through
    front -> codec round-trip -> back, paired with its OWN labels) — the
    warmup slots' zero payloads are masked out and contribute nothing."""
    r = run_py(COMMON + textwrap.dedent("""
        codec = build("c3sl:R=2", D=D)
        params = {"embed": embed_p, "blocks": blocks, "head": head_p,
                  "codec": codec.init(jax.random.PRNGKey(7))}
        l2, _ = run(2, codec, params)
        mb = B // M
        tot = 0.0
        for m in range(M):
            h = embed_fn(params["embed"], x[m*mb:(m+1)*mb])
            h = stage_fn(jax.tree.map(lambda a: a[0], params["blocks"]), h)
            Zf = h.reshape(mb, D)
            Zf = codec.decode(params["codec"], codec.encode(params["codec"], Zf))
            h = stage_fn(jax.tree.map(lambda a: a[1], params["blocks"]),
                         Zf.reshape(h.shape))
            tot = tot + head_loss_fn(params["head"], h, y[m*mb:(m+1)*mb])
        print(json.dumps({"pipe": float(l2), "ref": float(tot / M)}))
    """))
    assert abs(r["pipe"] - r["ref"]) < 1e-5 * max(1.0, abs(r["ref"])), r


def test_depth_adds_exactly_one_bubble_step_per_unit():
    """The scan runs M + depth steps — pinned through the compiled HLO's
    trip-count-aware collective stats: the channel ppermute fires once per
    scan step with a fixed payload, so total collective-permute bytes are
    exactly (M + depth) x payload_bytes for every depth."""
    r = run_py(COMMON + textwrap.dedent("""
        from repro.launch import hloparse

        codec = build("c3sl:R=2", D=D)
        params = {"embed": embed_p, "blocks": blocks, "head": head_p,
                  "codec": codec.init(jax.random.PRNGKey(7))}

        def permute_bytes(depth):
            lf = transport.make_pod_pipeline_loss_fn(
                embed_fn, stage_fn, head_loss_fn, codec, mesh,
                num_microbatches=M, async_depth=depth)
            with jax.set_mesh(mesh):
                compiled = jax.jit(lf).lower(params, batch).compile()
            a = hloparse.analyze(compiled.as_text())
            return a["coll_by_op"].get("collective-permute", 0.0)

        mb = B // M
        payload_bytes = codec.wire_bytes(mb)
        print(json.dumps({"p1": permute_bytes(1), "p3": permute_bytes(3),
                          "M": M, "payload": payload_bytes}))
    """))
    assert r["payload"] > 0
    assert r["p1"] == (r["M"] + 1) * r["payload"], r
    assert r["p3"] == (r["M"] + 3) * r["payload"], r


def test_asymmetric_link_on_the_pipeline_channel():
    """A ``bwd:`` codec on the pod channel: the forward loss is identical
    (the seam is identity), the backward ppermute's gradient payload is
    re-compressed, so grads differ from the mirrored run."""
    r = run_py(COMMON + textwrap.dedent("""
        codec = build("c3sl:R=2", D=D)
        params = {"embed": embed_p, "blocks": blocks, "head": head_p,
                  "codec": codec.init(jax.random.PRNGKey(7))}
        l1, g1 = run(2, codec, params)
        link = transport.build_link("c3sl:R=2 >> bwd:c3sl:R=2", D=D)
        lp = link.init(jax.random.PRNGKey(7))
        l2, g2 = run(2, link, dict(params, codec=lp))
        diff = float(sum(jnp.abs(a - b).sum() for a, b in
                         zip(jax.tree.leaves(g1["embed"]),
                             jax.tree.leaves(g2["embed"]))))
        print(json.dumps({"l1": float(l1), "l2": float(l2), "diff": diff}))
    """))
    assert r["l1"] == r["l2"], r
    assert r["diff"] > 0, r


def test_adaptive_link_rejected_by_pipeline():
    """The pipeline compiles ONE program; handing it an unresolved adaptive
    channel must fail loudly, not silently bake a bucket."""
    r = run_py(COMMON + textwrap.dedent("""
        link = transport.build_link(
            "adaptive:c3sl:R=4,min_R=2 >> bwd:c3sl:R=2", D=D)
        try:
            transport.make_pod_pipeline_loss_fn(
                embed_fn, stage_fn, head_loss_fn, link, mesh,
                num_microbatches=M)
            ok = False
        except ValueError as e:
            ok = "static" in str(e)
        # pin_link resolves it
        static = transport.pin_link(link)
        transport.make_pod_pipeline_loss_fn(
            embed_fn, stage_fn, head_loss_fn, static, mesh,
            num_microbatches=M)
        print(json.dumps({"ok": bool(ok), "pinned": static.spec()}))
    """))
    assert r["ok"], r
    assert r["pinned"] == "c3sl:R=2,D=24 >> bwd:c3sl:R=2,D=24", r
