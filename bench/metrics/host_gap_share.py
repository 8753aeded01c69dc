"""Engine loop (``runtime/enginecore.py`` ``_run_chunks``, ``_drive``,
the engines' seeding): idle time of the fullest device outside every
device program run, over the traced window, in percent: the device
waiting on the host (``bench.phases.runs``).  Moves ``teps``."""

from bench import phases
from bench import trace_reduce as tr


def read(ctx):
    window = ctx.hi - ctx.lo
    if window <= 0 or not ctx.busy[ctx.fullest]:
        return None
    run_list = phases.runs(ctx.trace, ctx.fullest)
    covered = tr.busy(list(ctx.leaves[ctx.fullest]) + run_list, ctx.lo,
                      ctx.hi)
    return 100.0 * (window - covered) / window
