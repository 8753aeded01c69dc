"""Queue waves (``kernels/wavefaa.py``): device time of the operations
under the ``repro.wavefaa`` scope over the device's busy time, on the
fullest device, in percent.  Moves ``teps``."""

from bench import trace_reduce as tr

SCOPE = "repro.wavefaa"


def read(ctx):
    t = tr.time_by(ctx.leaves[ctx.fullest], tr.in_scope(SCOPE), ctx.lo,
                   ctx.hi).get(SCOPE)
    busy = ctx.busy[ctx.fullest]
    if not t or not busy:
        return None
    return 100.0 * t / busy
