"""A whole run with the timed path broken underneath reads not correct,
for each fault a cell can have: a step that returns its state unchanged,
half of each wave left out, and an answer altered where it is produced.
(No cell exchanges data between chips, so that fault has no test.)"""

import pytest

from benchcells import cells, run

jax = pytest.importorskip("jax")


def _stale_state(inner):
    def step(*args):
        out = inner(*args)
        return (args[0],) + tuple(out[1:])
    return step


def _altered_answer(inner):
    def step(*args):
        out = inner(*args)
        leaves, tree = jax.tree_util.tree_flatten(out[0])
        leaves[0] = leaves[0].at[1].add(1)
        return (jax.tree_util.tree_unflatten(tree, leaves),) + tuple(out[1:])
    return step


def _half_wave(inner):
    import jax.numpy as jnp

    def step(*args):
        valid = args[-1]
        keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
        return inner(*args[:-1], valid & keep)
    return step


FAULTS = {"stale_state": _stale_state, "half_wave": _half_wave,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", cells())
def test_fault_reads_not_correct(name, fault):
    def plant(query):
        query.engine.step_fn = FAULTS[fault](query.engine.step_fn)

    r = run(name, plant=plant)
    assert not r["correct"], r["checks"]
    assert r["failed"] >= 1
