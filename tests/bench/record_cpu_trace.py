"""Record the small CPU trace that ``test_bench_trace_reduce.py`` reads.

    JAX_PLATFORMS=cpu python tests/bench/record_cpu_trace.py

Two searches of a small jitted program, each inside a ``bench.search``
span, with a ``bench.wait`` span between them; the trace is written to
``tests/bench/data/cpu_trace.xplane.pb``.  Re-recording changes every
number, so the test's hand-worked intervals must be redone with it.
"""

import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"


def main() -> None:
    @jax.jit
    def f(x):
        with jax.named_scope("repro.demo"):
            y = jnp.sin(x) @ x
        return y + 1

    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.search"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.001)
        jax.profiler.stop_trace()
        shutil.copy(next(Path(d).rglob("*.xplane.pb")), OUT)


if __name__ == "__main__":
    main()
