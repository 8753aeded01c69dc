"""Device-side wave compaction — segmented-scan child packing
(DESIGN.md § 4.4, paper § III).

The fused engines build each round's child wave ``batch × max_fanout``
lanes wide and historically scattered the full sparse block.  On power-law
graphs almost every lane is masked out, so the scatter width — and at mesh
scope the collective payload — is O(B·F) for O(n_child) live children:
the one regime where the host-compacted legacy path still won (BENCH_3,
kron at batch 1024).  This module closes it with the classic prefix-sum
stream compaction (Wald'11 ray wavefronts, our ``render_compaction``
baseline), run on device *inside* the jitted loop:

    rank   = exclusive prefix sum of the spawn mask      (the ballot scan)
    dense[rank[i]] = plane[i]   for every active lane i  (one drop-scatter)

Because the ranks are exactly the row-major ticket ranks ``wavefaa``
promises (Lemma III.1's order), the compacted wave installs with
*contiguous* tickets ``tail + [0, n_child)`` — bit-identical planes to the
sparse install, with the scatter width cut to the engine's capacity bound.

Two faces, bit-identical (asserted by tests):

* ``wave_compact`` — the Pallas kernel, mirroring ``wavefaa``: a grid of
  VREG-tiled mask blocks, the in-block ``cumsum`` rank, ONE scalar
  rank-base commit per block into an SMEM accumulator, and a masked
  drop-scatter into a full-width dense output block that persists across
  the (sequential) grid.  Blocks are up to ``BLOCK_LANES`` lanes so huge
  child waves don't pay per-step dispatch overhead.  v5e's Mosaic
  lowering refuses the ``cumsum`` and the scatter, so it is the
  interpret-mode reference only.
* ``compact_planes`` — the pure-jnp ``lax.associative_scan`` twin that
  every round engine inlines, exactly like ``ring_slots.enq_planes``
  twins ``ring_enqueue``.

Both return the TRUE popcount, not the clamped one: a wave whose live
children exceed the compact width necessarily overflows its engine (the
width is the engine's capacity bound — the dense-wave rule, DESIGN.md
§ 4.4), and the true count is what makes the overflow check agree with
the sparse path's, lane drops notwithstanding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import resolve_interpret

LANES = 8 * 128          # minimum block: one (8, 128) VREG tile
BLOCK_LANES = 512 * 128  # preferred block for huge waves (64 Ki lanes)


def compact_width(nlanes: int, bound: int, mode=None):
    """The dense-wave rule: the static compact width for an ``nlanes``-wide
    sparse child wave on an engine whose per-round install is bounded by
    ``bound`` live children (its capacity-class limit — any round spawning
    more must overflow).  Returns ``None`` when compaction should not
    engage: ``mode=False`` forces it off, ``mode=None`` (auto) engages
    only when the sparse wave is wider than the bound (otherwise
    compaction cannot shrink anything), ``mode=True`` forces it on with
    ``width = min(nlanes, bound)`` (tests exercise the packed path on
    small shapes this way)."""
    if mode is False or nlanes == 0:
        return None
    w = min(int(nlanes), int(bound))
    if mode is None and int(nlanes) <= w:
        return None
    return max(w, 1)


@functools.partial(jax.jit, static_argnames=("width",))
def compact_planes(mask, planes, *, width: int):
    """Pure-jnp twin of ``wave_compact`` (shard_map/interpret paths).

    ``mask``: (N,) int32/bool spawn mask; ``planes``: tuple of (N,) int32
    value planes sharing the mask.  Returns ``(dense, count)`` where
    ``dense`` is a tuple of (width,) planes holding each input's active
    lanes packed in row-major rank order (rank ≥ width drops; tail lanes
    are zero) and ``count`` is the TRUE popcount — it may exceed
    ``width``, which callers must fold into their overflow check."""
    m = (jnp.asarray(mask) > 0).astype(jnp.int32)
    inc = jax.lax.associative_scan(jnp.add, m)   # inclusive prefix popcount
    rank = inc - m                               # exclusive rank
    idx = jnp.where((m > 0) & (rank < width), rank, width)
    dense = tuple(
        jnp.zeros((width,), jnp.int32).at[idx].set(
            jnp.asarray(p, jnp.int32), mode="drop")
        for p in planes)
    return dense, jnp.sum(m)


def _compact_kernel(width, nplanes, block, mask_ref, *refs):
    plane_refs = refs[:nplanes]
    dense_refs = refs[nplanes:2 * nplanes]
    count_ref = refs[2 * nplanes]
    acc_ref = refs[2 * nplanes + 1]
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[0] = 0
        for d in dense_refs:
            d[...] = jnp.zeros_like(d)

    m = (mask_ref[...] > 0).astype(jnp.int32)        # (rows, 128) block
    flat = m.reshape(1, block)
    rank = jnp.cumsum(flat, axis=1) - flat           # in-block exclusive rank
    base = acc_ref[0]
    # ranks past the dense width drop (the wave must overflow its engine;
    # the true count below keeps that check exact)
    idx = jnp.where(flat > 0, base + rank, width)
    for p, d in zip(plane_refs, dense_refs):
        v = p[...].reshape(1, block)
        d[...] = d[...].at[0, idx[0]].set(v[0], mode="drop")
    # ONE commit per block — the same aggregation step as wavefaa
    acc_ref[0] = base + jnp.sum(m)

    @pl.when(step == pl.num_programs(0) - 1)
    def _fin():
        count_ref[0] = acc_ref[0]


def wave_compact(mask, planes, *, width: int, interpret=None):
    """Ballot-compact ``planes`` by ``mask`` into (width,) dense waves —
    the Pallas face.  Same contract and bit-identical results as
    ``compact_planes`` (rank ≥ width drops, TRUE popcount returned);
    ``interpret=None`` resolves via REPRO_PALLAS_INTERPRET / backend.
    Arbitrary N — the wrapper zero-pads to the block grid.  Not on the
    engine path: v5e refuses its in-kernel ``cumsum`` and scatter; engines
    call ``compact_planes``."""
    return _wave_compact_jit(mask, tuple(planes), width=int(width),
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _wave_compact_jit(mask, planes, *, width: int, interpret: bool):
    n = mask.shape[0]
    block = LANES if n <= BLOCK_LANES else BLOCK_LANES
    npad = -(-max(n, 1) // block) * block
    m = (jnp.asarray(mask) > 0).astype(jnp.int32)
    if npad != n:
        m = jnp.zeros((npad,), jnp.int32).at[:n].set(m)
        planes = tuple(jnp.zeros((npad,), jnp.int32).at[:n].set(
            jnp.asarray(p, jnp.int32)) for p in planes)
    else:
        planes = tuple(jnp.asarray(p, jnp.int32) for p in planes)
    blocks, rows = npad // block, block // 128
    wpad = -(-width // 128) * 128               # dense block: 128-lane tiles
    nplanes = len(planes)
    kern = functools.partial(_compact_kernel, width, nplanes, block)
    call = pl.pallas_call(
        kern,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0))] * (1 + nplanes),
        out_specs=[pl.BlockSpec((1, wpad), lambda i: (0, 0))] * nplanes
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((1, wpad), jnp.int32)] * nplanes
        + [jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )
    with jax.named_scope("repro.wave_compact"):
        outs = call(m.reshape(blocks * rows, 128),
                    *[p.reshape(blocks * rows, 128) for p in planes])
    dense = tuple(o.reshape(wpad)[:width] for o in outs[:nplanes])
    return dense, outs[nplanes][0]
