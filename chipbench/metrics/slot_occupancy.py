"""Time-weighted mean share of the engine's slots holding a request over
the window, sampled between engine ticks from ``engine.slots``."""


def read(ctx):
    if ctx["conf"]["kind"] != "serve":
        return None
    return 100.0 * ctx["served"].occupancy
