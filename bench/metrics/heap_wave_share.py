"""App step and G-PQ (``kernels/heap_batch.py`` pop and insert waves,
the claim schedule with the pops): device time of the operations under
``repro.heap.pop`` or ``repro.heap.insert`` over the device's busy time,
on the fullest device, in percent (``bench.phases``).  Moves ``teps``."""

from bench import phases


def read(ctx):
    return phases.share_of_busy(ctx, phases.HEAP)
