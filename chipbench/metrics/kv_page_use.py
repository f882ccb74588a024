"""Share of the KV page pool holding written positions, time-weighted:
page-seconds of pages that hold a written position of a resident
request (``kv_written_page_s``) over page-seconds of the whole pool
(``kv_pool_page_s``), both integrated by the engine between its state
reads at boundaries."""
from chipbench.engine_stats import window_ratio


def read(ctx):
    return window_ratio(ctx, "kv_written_page_s", "kv_pool_page_s", 100.0)
