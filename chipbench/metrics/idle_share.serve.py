"""Share of the traced serving window in which no operation ran on the
chip: 1 - (union of device op intervals) / window."""


def read(ctx):
    if ctx["conf"]["kind"] != "serve" or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
