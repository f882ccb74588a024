"""Unit tests for the trip-count-aware HLO analyzer (roofline source)."""
import textwrap

from repro.launch import hloparse

SAMPLE = textwrap.dedent("""\
    HloModule test

    %add (x: f32[], y: f32[]) -> f32[] {
      %x = f32[] parameter(0)
      %y = f32[] parameter(1)
      ROOT %s = f32[] add(%x, %y)
    }

    %body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
      %p = (s32[], f32[8,16]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %a = f32[8,16]{1,0} get-tuple-element(%p), index=1
      %w = f32[16,16]{1,0} constant(0)
      %d = f32[8,16]{1,0} dot(%a, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,16]{1,0} all-reduce(%d), to_apply=%add
      %one = s32[] constant(1)
      %i2 = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,16]) tuple(%i2, %ar)
    }

    %cond (p: (s32[], f32[8,16])) -> pred[] {
      %p = (s32[], f32[8,16]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %n = s32[] constant(10)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    ENTRY %main (arg: f32[8,16]) -> f32[8,16] {
      %arg = f32[8,16]{1,0} parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[8,16]) tuple(%zero, %arg)
      %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body
      ROOT %out = f32[8,16]{1,0} get-tuple-element(%w), index=1
    }
""")


def test_trip_count_and_flops():
    t = hloparse.analyze(SAMPLE)
    # dot: 2 * 8*16 out * 16 contract = 4096 flops, x10 trips
    assert t["dot_flops"] == 2 * 8 * 16 * 16 * 10
    # all-reduce: 8*16*4 bytes x10
    assert t["coll_bytes"] == 8 * 16 * 4 * 10
    assert t["coll_by_op"]["all-reduce"] == 8 * 16 * 4 * 10


def test_collective_bytes_counts_tuple_shapes():
    txt = "%x = (f32[4,4]{1,0}, f32[2]{0}) all-gather(%a, %b), dims={0}\n"
    from repro.launch.dryrun import collective_bytes
    out = collective_bytes(txt)
    assert out["all-gather"] == (16 + 2) * 4


def test_header_param_order_handles_tuples():
    hdr = "%c (a: (s32[], f32[2,2]), b: f32[4]) -> pred[] {"
    assert hloparse._header_param_order(hdr) == ["a", "b"]


# ---------------------------------------------------------------------------
# mask-aware (measured) top-k wire accounting
# ---------------------------------------------------------------------------

def test_topk_wire_bytes_from_custom_call_line():
    ln = ('%custom-call = (f32[20,8]{1,0}, s32[20,8]{1,0}) '
          'custom-call(%abs.40), custom_call_target="TopK"')
    defs = {"abs.40": "%abs.40 = f32[20,64]{1,0} abs(%x)"}
    # 20 rows x (64-bit mask -> 8 bytes + 8 f32 survivors -> 32 bytes),
    # the operand's shape resolved through the defs map
    assert hloparse._topk_wire_bytes_for_line(ln, defs) == 20 * (64 // 8 + 4 * 8)
    # a top-k of anything but |x| is not a wire payload
    raw = dict(defs, **{"abs.40": "%abs.40 = f32[20,64]{1,0} add(%x, %y)"})
    assert hloparse._topk_wire_bytes_for_line(ln, raw) == 0.0
    # non-topk custom calls measure nothing
    assert hloparse._topk_wire_bytes_for_line(
        '%cc = f32[4]{0} custom-call(%x), '
        'custom_call_target="Other"', defs) == 0.0


def test_topk_wire_bytes_excludes_router_topk():
    """Only MAGNITUDE top-ks (the wire stage ranks |payload|) count as
    sparsified payload — a MoE router's top-k over raw logits is program
    control flow and must not pollute the measured codec bytes."""
    import jax
    import jax.numpy as jnp

    txt = jax.jit(lambda z: jax.lax.top_k(z, 2)).lower(
        jnp.zeros((64, 16))).compile().as_text()
    assert "TopK" in txt                            # the op IS there
    assert hloparse.analyze(txt)["topk_wire_bytes"] == 0.0


def test_topk_wire_bytes_measured_from_compiled_hlo():
    """Cross-check the ROADMAP item end-to-end: wire bytes of a sparsified
    payload MEASURED from the lowered program equal the analytic
    ``payload_wire_bytes`` — rows/k/D all read off the real top-k op."""
    import jax
    import jax.numpy as jnp
    from repro import codecs
    from repro.codecs import build

    codec = build("c3sl:R=4,D=64|topk:k=8")
    p = codec.init(jax.random.PRNGKey(0))
    z = jnp.zeros((80, 64))
    txt = jax.jit(lambda z: codec.encode(p, z)).lower(z).compile().as_text()
    measured = hloparse.analyze(txt)["topk_wire_bytes"]
    analytic = codecs.payload_wire_bytes(codec, codec.payload_shape(80))
    assert measured == analytic == (80 // 4) * (64 // 8 + 4 * 8)


def test_topk_wire_bytes_trip_count_aware():
    """A top-k inside a scan body multiplies by the loop trip count, like
    every other per-computation stat (the encode must be loop-variant or
    XLA hoists it — which the measurement would faithfully report as 1x)."""
    import jax
    import jax.numpy as jnp
    from repro import codecs
    from repro.codecs import build

    codec = build("c3sl:R=4,D=64|topk:k=8")
    p = codec.init(jax.random.PRNGKey(0))
    z = jnp.zeros((80, 64))

    def scanned(z):
        def body(c, i):
            return c + 1.0, codec.encode(p, z + i)
        _, ys = jax.lax.scan(body, 0.0, jnp.arange(5.0))
        return ys

    txt = jax.jit(scanned).lower(z).compile().as_text()
    analytic = codecs.payload_wire_bytes(codec, codec.payload_shape(80))
    assert hloparse.analyze(txt)["topk_wire_bytes"] == 5 * analytic
