"""Queue waves (``kernels/wavefaa.py``): the least time the chip could
take for the traced ``wavefaa`` calls (each over the cell's padded lane
count; ``bench.peaks.wavefaa_work``) over their device time, in percent.
The calls are the trace's operations under the ``repro.wavefaa`` scope,
one a round.  The bound is memory: 8 bytes a lane against one
operation.  Moves ``teps``."""

from bench import trace_reduce as tr
from bench.peaks import roofline_seconds, wavefaa_work

SCOPE = "repro.wavefaa"


def read(ctx):
    lanes = ctx.info.get("wavefaa_lanes")
    key = tr.in_scope(SCOPE)
    ops = [ev for ev in ctx.leaves[ctx.fullest] if key(ev)
           and ctx.lo <= ev.start and ev.end <= ctx.hi]
    calls, t = len(ops), sum(ev.end - ev.start for ev in ops)
    if not lanes or not calls or not t or ctx.peaks is None:
        return None
    least, _bound = roofline_seconds(wavefaa_work(lanes), ctx.peaks)
    return 100.0 * calls * least / (t * 1e-9)
