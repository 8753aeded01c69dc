"""Run one cell of the benchmark once on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``: each number compared with
the plain reference beside its limit, which also end standard error.
Refuses to start (exit 2, no result) without a TPU, with fewer chips than
the cell asks for, or with Pallas kernels in interpret mode.

``--control`` plants the control (a queue that loses items) in the timed
path; its result must read ``"correct": false``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _refuse(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, manifest
    # libtpu would log under /tmp/tpu_logs, outside the run's directories
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    cell = manifest.cell(args.workload, ROOT)
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        return _refuse("REPRO_PALLAS_INTERPRET is set: the benchmark runs "
                       "compiled Pallas kernels only")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _refuse(f"no TPU: JAX's first device is "
                       f"{devices[0].platform}")
    if len(devices) < cell.spec["chips"]:
        return _refuse(f"{args.workload} needs {cell.spec['chips']} chips, "
                       f"JAX sees {len(devices)}")
    try:
        from repro.compile_cache import use_compile_cache
        from repro.kernels.pallas_env import resolve_interpret
    except ImportError as e:
        return _refuse(f"the program is not here: {e}")
    if resolve_interpret(None):
        return _refuse("Pallas kernels resolve to interpret mode")
    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), devices, T_START,
        plant=harness.control if args.control else None)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
