"""App step and G-PQ (``apps/sssp.py`` relaxed delta-stepping): items
the engine popped (``stats["processed"]``) per vertex reached, over the
traced searches.  One is the least; the rest is re-expansion that relaxed
order and the batch cost.  Moves ``teps``."""


def read(ctx):
    pops = sum(s.stats.get("processed", 0) for s in ctx.searches)
    reached = sum(ctx.checks[s.index].reached for s in ctx.searches
                  if s.index in ctx.checks)
    if not pops or not reached:
        return None
    return pops / reached
