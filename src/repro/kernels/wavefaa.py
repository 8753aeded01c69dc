"""WAVEFAA as a Pallas TPU kernel — vectorized aggregate-then-commit ticket
reservation (paper Alg. 1 / Fig. 1, adapted per DESIGN.md § 2.1).

On the GPU a wavefront ballots, one leader FAAs by the popcount, and lanes
add their prefix rank.  On TPU the "wave" is a VMEM-resident block of request
lanes: the kernel computes the in-block exclusive prefix rank on the VREG
lane grid and commits **one** scalar counter update per block into an SMEM
accumulator that carries across the (sequential) TPU grid — the same
aggregation hierarchy, one level up.

Block shape: (8, 128) int32 lanes per grid step — one VREG tile.  The mask
is reshaped (N,) → (N/1024, 8, 128) by the wrapper.  The in-block prefix
rank is two triangular f32 matmuls rather than ``cumsum``, which the v5e
Mosaic lowering does not implement; integer counts are exact in f32 far
past the 1024 lanes of a block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import resolve_interpret

LANES = 8 * 128  # one (8, 128) VREG tile per grid step


def exclusive_rank(a: jax.Array) -> jax.Array:
    """Row-major exclusive prefix sum of an (R, 128) int32 tile: the in-row
    prefix is a strictly-upper-triangular matmul, each row's offset the sum
    of the totals of the rows above it.  Exact while the tile sums below
    2^24 (f32 mantissa, HIGHEST precision)."""
    rows, cols = a.shape
    af = a.astype(jnp.float32)
    k = jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 1)
    rank = jnp.dot(af, (k < j).astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    totals = jnp.sum(af, axis=1, keepdims=True)     # (R, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    for q in range(rows - 1):
        rank = rank + jnp.where(row > q, totals[q:q + 1, :], 0.0)
    return rank.astype(jnp.int32)


def _wavefaa_kernel(counter_ref, active_ref, tickets_ref, newctr_ref, acc_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[0] = counter_ref[0]

    a = active_ref[...].astype(jnp.int32)           # (8, 128) block
    base = acc_ref[0]
    tickets_ref[...] = jnp.where(a > 0, base + exclusive_rank(a), -1)
    # ONE commit per block — the leader FAA of Alg. 1
    acc_ref[0] = base + jnp.sum(a)

    @pl.when(step == pl.num_programs(0) - 1)
    def _fin():
        newctr_ref[0] = acc_ref[0]


def wavefaa(active: jax.Array, counter: jax.Array, *, interpret=None):
    """active: (N,) int32/bool with N % 1024 == 0; counter: (1,) int32.
    ``interpret=None`` resolves via REPRO_PALLAS_INTERPRET / backend.
    Returns (tickets (N,) int32, new_counter (1,) int32)."""
    return _wavefaa_jit(active, counter,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _wavefaa_jit(active: jax.Array, counter: jax.Array, *, interpret: bool):
    n = active.shape[0]
    assert n % LANES == 0, f"N={n} must be a multiple of {LANES}"
    blocks = n // LANES
    a = active.astype(jnp.int32).reshape(blocks * 8, 128)
    ctr = counter.astype(jnp.int32).reshape(1)
    call = pl.pallas_call(
        _wavefaa_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((8, 128), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((8, 128), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((blocks * 8, 128), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )
    with jax.named_scope("repro.wavefaa"):
        tickets, newctr = call(ctr, a)
    return tickets.reshape(n), newctr
