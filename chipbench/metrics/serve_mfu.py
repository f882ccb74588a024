"""Model FLOPs of every real token the engine processed in the traced
window (prompt tokens through every layer, the head only where a token is
sampled; decode tokens through every layer and the head; attention over
each token's cached length), over the window, over the chip's peak.
Padding rows of a prefill chunk and idle decode slots are not work."""
from chipbench import counts
from chipbench.drivers import serve


def read(ctx):
    if ctx["conf"]["kind"] != "serve":
        return None
    m = ctx["conf"]["model"]
    flops = 0
    for kind, rows in serve.log_in(ctx["log"], *ctx["trace_window"]):
        if kind == "P":
            for _, _, start, n in rows:
                for p in range(start, start + n):
                    flops += counts.token_flops(m, p + 1, head=False)
        else:
            for _, _, pos in rows:
                flops += counts.token_flops(m, pos + 1, head=True)
    if flops == 0:
        return None
    peak = ctx["peaks"]["bf16_flops"] * len(ctx["devices"])
    return 100.0 * flops / ctx["window_s"] / peak
