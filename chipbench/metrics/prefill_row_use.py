"""Share of the rows the engine's prefill chunks dispatched (slots x
chunk size per chunk, ``prefill_rows``) that carried prompt tokens
(``prefill_tokens``): a chunk costs the same whatever its real rows."""
from chipbench.engine_stats import window_ratio


def read(ctx):
    return window_ratio(ctx, "prefill_tokens", "prefill_rows", 100.0)
