"""95th percentile of the gaps between consecutive token arrivals at the
clients in the window (tokens of one TOKENS frame have gap 0).

A tail of a closed loop that keeps every slot busy: it swings with the
order in which requests meet, so it is recorded per run and not judged
by a bound."""
from chipbench.drivers import serve


def read(ctx):
    if ctx["conf"]["kind"] != "serve":
        return None
    return serve.end_to_end(ctx["served"])[0]["itl_p95_ms"]["value"]
