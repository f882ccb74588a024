"""Ratios of the engine's own counters (``BatchedEngine.stats``) over the
window, from the snapshots ``drivers/serve.py`` takes at its start and end
(``served.stats0``, ``served.stats1``)."""


def window_ratio(ctx, num: str, den: str, scale: float = 1.0):
    """``scale * d(num) / d(den)`` over the window; None where the program
    keeps no such counters or ``den`` did not move."""
    served = ctx.get("served")
    s0 = getattr(served, "stats0", None)
    s1 = getattr(served, "stats1", None)
    if not s0 or not s1 or num not in s1 or den not in s1:
        return None
    d = s1[den] - s0[den]
    if d <= 0:
        return None
    return scale * (s1[num] - s0[num]) / d
