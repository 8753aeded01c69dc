"""Engine loop (``runtime/enginecore.py`` ``fused_loop``, ``_drive``):
host seconds of the traced searches over the rounds their engine counted,
in microseconds.  Moves ``teps``."""


def read(ctx):
    rounds = sum(s.stats.get("rounds", 0) for s in ctx.searches)
    if not rounds:
        return None
    seconds = sum(s.end - s.start for s in ctx.searches)
    return seconds / rounds * 1e6
