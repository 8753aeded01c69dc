"""What every query kind shares: the warm-up, the engine's counters per
search, and freeing the program's state before the references run.

A kind (``bench/queries/<kind>.py``) subclasses ``Query`` and gives
``runner``, ``engine`` (whose ``step_fn`` the control and the fault
tests wrap), ``spec``, ``info``, ``_run(i, max_rounds)`` (search ``i``
through the program's entry point), ``search(i)`` and ``check(i, out)``,
and ``pass_length``: the searches of one pass over the cell's traffic,
which the window runs whole.
"""

from __future__ import annotations


class Query:
    pass_length = 1

    def warm(self) -> None:
        """Compile and run every program of the cell's shapes: a few rounds
        (``warm_rounds``) of two searches; the engine raises on the
        truncation, which is the point."""
        for i in range(2):
            try:
                self._run(i, self.spec["warm_rounds"])
            except RuntimeError as e:
                if "truncated" not in str(e):
                    raise

    def stats(self) -> dict:
        return dict(self.runner.stats)

    def release(self) -> None:
        self.runner = self.engine = None
