"""90th percentile, over the requests whose result arrived in the window, of
the time from send to the first token's arrival at the client.

A tail of a closed loop that keeps every slot busy: it swings with the
order in which requests meet, so it is recorded per run and not judged
by a bound."""
from chipbench.drivers import serve


def read(ctx):
    if ctx["conf"]["kind"] != "serve":
        return None
    return serve.end_to_end(ctx["served"])[0]["ttft_p90_ms"]["value"]
