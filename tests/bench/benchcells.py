"""The benchmark's cells cut to sizes a CPU test run holds, for the
control and fault tests.  Only sizes change; the path, the traffic and
the comparison are the cells' own."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest as mf  # noqa: E402

SMALL_SPEC = {
    "bfs": {"grid_side": 12, "batch": 8, "roots": [[0, 0], [4, 2], [6, 6]]},
    "sssp": {"grid_side": 8, "batch": 8, "roots": [[0, 0], [3, 1]]},
}
SEED = 2 ** 31 + 11


def small_cell(name: str, **spec) -> mf.Cell:
    cell = mf.cell(name, ROOT)
    kind = cell.spec["query"]
    return cell._replace(spec={**cell.spec, **SMALL_SPEC[kind], **spec})


def cells():
    return [w["name"] for w in mf.manifest(ROOT)["workloads"]]


def run(name: str, *, seconds: float = 0.3, trace: bool = False,
        plant=None, seed: int = SEED, **spec) -> dict:
    import io
    import time

    import jax
    from bench import harness
    return harness.run_cell(small_cell(name, **spec), seed, seconds, trace,
                            jax.devices(), time.perf_counter(), plant=plant,
                            log=io.StringIO())
