"""Entry and app step (the application's ``step_fn``: BFS's claim and
neighbour expansion, SSSP's relaxation): device time of the operations
under ``repro.step`` over the device's busy time, on the fullest device,
in percent (``bench.phases``).  Moves ``teps``."""

from bench import phases


def read(ctx):
    return phases.share_of_busy(ctx, (phases.STEP,))
