"""Queue waves (``kernels/ring_slots.py`` ``deq_planes``/``enq_planes``,
and ``compact_planes`` when it engages): device time of the operations
under ``repro.ring.deq`` or ``repro.ring.enq`` over the device's busy
time, on the fullest device, in percent (``bench.phases``).  Moves
``teps``."""

from bench import phases


def read(ctx):
    return phases.share_of_busy(ctx, phases.RING)
