"""Graph500 TEPS: input edges traversed, summed over the window's
searches, over the window's seconds (host clock).  A search's edges are
the undirected edges of its root's component, counted from the
benchmark's reference, so wasted pops lower it."""


def read(run):
    edges = sum(run.checks[s.index].edges for s in run.searches
                if s.index in run.checks)
    return edges / run.window_s
