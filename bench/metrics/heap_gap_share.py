"""App step and G-PQ: idle time inside the megaround where the
operations on both sides of the gap are heap waves (``repro.heap.*``),
over the traced window, on the fullest device, in percent: the device
waiting between the sift loops' small dependent operations
(``bench.phases.scoped_gaps``).  Moves ``teps``."""

from bench import phases


def read(ctx):
    ph = phases.of(ctx)
    window = ctx.hi - ctx.lo
    if ph is None or window <= 0:
        return None
    if not any(p in phases.HEAP for p in ph.phase):
        return None
    phase_of = {id(ev): p for ev, p in zip(ph.leaves, ph.phase)}
    mega = [r for r in ph.runs if r.name == ph.module] or ph.runs
    idle = sum(e - s for s, e, before, after
               in phases.scoped_gaps(ph.leaves, ctx.lo, ctx.hi, mega)
               if phase_of[id(before)] in phases.HEAP
               and phase_of[id(after)] in phases.HEAP)
    return 100.0 * idle / window
