"""Share of the KV page pool reserved by resident requests, time-weighted:
page-seconds of the pages that admission set aside for resident requests
(``kv_reserved_page_s``, prompt + max_new each) over page-seconds of the
whole pool (``kv_pool_page_s``), both integrated by the engine between its
state reads at boundaries.  Admission waits on reserved pages, not written
ones, so this is the share that says whether the pool holds it back."""
from chipbench.engine_stats import window_ratio


def read(ctx):
    return window_ratio(ctx, "kv_reserved_page_s", "kv_pool_page_s", 100.0)
