"""Share of its roofline the C3-SL codec's Pallas bind and unbind kernels
reached in the traced window: the least time of their work (FFT-count
FLOPs over the peak, or the rows in, the keys and the payload out over the
bandwidth, whichever is larger) over the kernels' summed device time."""
from chipbench import counts
from chipbench.drivers import serve

# named by the trace after the jitted wrappers of repro.kernels.circconv
KERNELS = r"^(bind_superpose_kernel|unbind_kernel)(\.|$)"


def read(ctx):
    conf = ctx["conf"]
    if conf["kind"] != "serve" or "backend=pallas" not in conf["link"]["spec"]:
        return None
    e, R, D = conf["engine"], conf["link"]["R"], conf["model"]["hidden_size"]
    pk = ctx["peaks"]
    least = 0.0
    for kind, _ in serve.log_in(ctx["log"], *ctx["trace_window"]):
        G = e["num_slots"] // R * (e["chunk_size"] if kind == "P" else 1)
        ops, byts = counts.circconv_call(G, R, D)
        least += 2 * max(ops / pk["bf16_flops"], byts / pk["hbm_bytes_per_s"])
    spent = ctx["trace"].kernel_seconds(ctx["devices"], KERNELS)
    if least == 0 or spent == 0:
        return None
    return 100.0 * least / spent
