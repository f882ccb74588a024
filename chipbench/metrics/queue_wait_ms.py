"""Mean time a request admitted in the window waited in the engine's
queue: from ``submit`` (or its re-queue after an eviction) to its
admission into a slot, summed by the engine (``queue_wait_s``) over the
admissions it counts (``admitted``)."""
from chipbench.engine_stats import window_ratio


def read(ctx):
    return window_ratio(ctx, "queue_wait_s", "admitted", 1e3)
