"""HLO analyzer validation: trip-count extraction and FLOP accounting on a
known scanned workload (the probe that motivated the analyzer: XLA's
cost_analysis counts while bodies once)."""

import jax
import jax.numpy as jnp

from repro.jaxcompat import make_mesh
from repro.launch.hlo_analysis import analyze_hlo_text


def test_scan_trip_count_multiplies_flops():
    L, M, B = 7, 128, 32

    def step(w, xs):
        def body(c, x):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, xs[0], xs, length=L)
        return c.sum()

    w = jax.ShapeDtypeStruct((M, M), jnp.float32)
    xs = jax.ShapeDtypeStruct((L, B, M), jnp.float32)
    compiled = jax.jit(jax.grad(step)).lower(w, xs).compile()
    c = analyze_hlo_text(compiled.as_text())
    assert not c.warnings, c.warnings
    # fwd: L×(2·B·M·M); bwd ≈ 2× more (dgrad + wgrad)
    fwd = L * 2 * B * M * M
    assert c.flops >= 2.5 * fwd, (c.flops, fwd)
    assert c.flops <= 4.0 * fwd, (c.flops, fwd)
    # cost_analysis counts the body once — the analyzer must exceed it
    assert c.flops > float(compiled.cost_analysis()["flops"]) * (L - 1) / 2


def test_collectives_counted():
    import numpy as np
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P
    def f(x):
        return jax.lax.psum(x, "data")

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P()))
    compiled = g.lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
    c = analyze_hlo_text(compiled.as_text())
    assert c.collective_bytes >= 0  # single device may elide the collective


def test_shape_parsing():
    from repro.launch.hlo_analysis import shape_bytes, shape_elems
    assert shape_bytes("f32[2,3]{1,0}") == 24
    assert shape_bytes("bf16[10]") == 20
    assert shape_bytes("(f32[2]{0}, s32[])") == 12
    assert shape_elems("pred[8,8]") == 64
