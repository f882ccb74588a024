"""Share of its roofline the Pallas paged-attention decode read reached
in the traced window: the least time its work needs (the larger of its
FLOPs over the peak and, usually, its bytes over the bandwidth: the K and
V of the positions each live slot attends, read once, plus q and out)
over the summed device time of the kernel's ops."""
from chipbench import counts
from chipbench.drivers import serve

# The kernel's custom call, named by the trace after its enclosing call:
# a Mosaic call whose operands open with the two scalar-prefetch operands,
# the (B, P) int32 page table and the (B,) int32 positions.
KERNEL_TEXT = (r'custom-call\(s32\[\d+,\d+\]\S* %\S+, s32\[\d+\]\S* %\S+, '
               r'.*custom_call_target="tpu_custom_call"')


def read(ctx):
    if ctx["conf"]["kind"] != "serve" or ctx["conf"]["engine"]["kv_read"] != "kernel":
        return None
    m = ctx["conf"]["model"]
    pk = ctx["peaks"]
    least = 0.0
    for kind, rows in serve.log_in(ctx["log"], *ctx["trace_window"]):
        if kind != "D":
            continue
        ops, byts = counts.paged_attention_call(m, [pos for _, _, pos in rows])
        least += m["num_hidden_layers"] * max(ops / pk["bf16_flops"],
                                              byts / pk["hbm_bytes_per_s"])
    spent = ctx["trace"].kernel_seconds(ctx["devices"], text=KERNEL_TEXT)
    if least == 0 or spent == 0:
        return None
    return 100.0 * least / spent
