"""Batched d-ary heap operations as a Pallas TPU kernel (DESIGN.md § 5.6).

The device face of G-PQ, mirroring ``ring_slots.py``: the heap's packed
node words are unpacked into two parallel int32 field planes (key / val —
TPU-native 32-bit lanes) living in VMEM, and one kernel invocation applies
a *ticket-ordered batch* of operations — the wave's announce-ring drain
plus its delete-mins — in batch-index order, which is the linearization
order (the deterministic analogue of the latch-combined drain).

Each op is ``(opcode, key, val)``: opcode 0 = INSERT (sift-up, rejected
when full), 1 = DELETE-MIN (root out, last node sifts down, rejected when
empty), anything else = inactive lane padding.  Sifts are fixed-trip
``fori_loop``s over the heap's static depth with a moving flag — no
data-dependent control flow, so the kernel compiles to straight-line TPU
code.  The heap size rides in SMEM alongside the op batch.

``heap_planes`` is the pure-jnp twin of the kernel — the same masked
batched sift expressed as ``lax.scan``/``fori_loop`` plane updates, so the
mesh engine can inline heap batches into a jitted ``while_loop`` *under
shard_map* exactly as the FIFO engine inlines ``ring_slots.enq_planes``.
Both faces are bit-identical (asserted by differential tests), and both
honor inactive (``OP_NOP``) lanes, which is what makes *partial waves*
work: ``heap_pop_count`` pops a traced-count prefix of a fixed-width
batch, ``heap_insert_masked`` installs a masked subset — the claim and
publish waves of the priority mesh rounds (DESIGN.md § 6).

VMEM: the Pallas kernel holds 2 planes × 2^cap_log2 × 4 B plus the batch
in VMEM, in and out, and indexes them with dynamic scalar loads and
stores, which v5e's Mosaic lowering refuses ("Cannot store scalars to
VMEM").  The round engines therefore run ``heap_planes`` on every
backend; ``heap_apply`` stays as the differential reference of the
interpret-mode tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import resolve_interpret

KEY_INF = 2 ** 31 - 1    # empty-slot / inactive-lane key sentinel

OP_INSERT, OP_DELMIN, OP_NOP = 0, 1, -1


def _heap_kernel(cap_log2, arity_log2, size_ref, ops_ref, okeys_ref,
                 ovals_ref, keys_in, vals_in, keys_ref, vals_ref,
                 outk_ref, outv_ref, ok_ref, size_out_ref):
    cap = 1 << cap_log2
    d = 1 << arity_log2
    # static depth: levels needed to cover cap nodes with arity d
    max_depth = -(-cap_log2 // arity_log2) + 1
    keys_ref[...] = keys_in[...]
    vals_ref[...] = vals_in[...]
    outk_ref[...] = jnp.full_like(outk_ref, KEY_INF)
    outv_ref[...] = jnp.full_like(outv_ref, -1)
    ok_ref[...] = jnp.zeros_like(ok_ref)
    b = ops_ref.shape[1]

    def body(i, size):
        op = ops_ref[0, i]
        key = okeys_ref[0, i]
        val = ovals_ref[0, i]

        # ---- INSERT: hole starts at `size`, parents move down ----------
        do_ins = (op == OP_INSERT) & (size < cap)

        def up(_, carry):
            j, moving = carry
            p = jnp.where(j > 0, (j - 1) >> arity_log2, 0)
            pk = keys_ref[0, p]
            cond = moving & (j > 0) & (pk > key)
            jc = jnp.where(cond, j, 0)          # clamp for the masked store
            keys_ref[0, jc] = jnp.where(cond, pk, keys_ref[0, jc])
            vals_ref[0, jc] = jnp.where(cond, vals_ref[0, p], vals_ref[0, jc])
            return (jnp.where(cond, p, j), moving & cond)

        j0 = jnp.where(do_ins, size, 0)
        jf, _ = jax.lax.fori_loop(0, max_depth, up, (j0, do_ins))
        keys_ref[0, jf] = jnp.where(do_ins, key, keys_ref[0, jf])
        vals_ref[0, jf] = jnp.where(do_ins, val, vals_ref[0, jf])

        # ---- DELETE-MIN: root out, last node sifts down into the hole --
        do_pop = (op == OP_DELMIN) & (size > 0)
        outk_ref[0, i] = jnp.where(do_pop, keys_ref[0, 0], KEY_INF)
        outv_ref[0, i] = jnp.where(do_pop, vals_ref[0, 0], -1)
        nsize = jnp.where(do_pop, size - 1, size)
        lpos = jnp.where(do_pop & (size > 0), size - 1, 0)
        lk = keys_ref[0, lpos]
        lv = vals_ref[0, lpos]

        def down(_, carry):
            j, moving = carry
            base = (j << arity_log2) + 1

            def child(c, acc):
                bk, bj = acc
                cj = base + c
                in_r = cj < nsize
                ck = jnp.where(in_r, keys_ref[0, jnp.where(in_r, cj, 0)],
                               KEY_INF)
                better = ck < bk
                return (jnp.where(better, ck, bk), jnp.where(better, cj, bj))

            bk, bj = jax.lax.fori_loop(0, d, child, (KEY_INF, -1))
            cond = moving & (bj >= 0) & (bk < lk)
            jc = jnp.where(cond, j, 0)
            keys_ref[0, jc] = jnp.where(cond, bk, keys_ref[0, jc])
            vals_ref[0, jc] = jnp.where(
                cond, vals_ref[0, jnp.where(cond, bj, 0)], vals_ref[0, jc])
            return (jnp.where(cond, bj, j), moving & cond)

        moving0 = do_pop & (nsize > 0)
        jf2, _ = jax.lax.fori_loop(0, max_depth, down, (0, moving0))
        place = jnp.where(moving0, jf2, 0)
        keys_ref[0, place] = jnp.where(moving0, lk, keys_ref[0, place])
        vals_ref[0, place] = jnp.where(moving0, lv, vals_ref[0, place])
        # scrub the vacated tail slot so stale keys can't resurface
        keys_ref[0, lpos] = jnp.where(do_pop, KEY_INF, keys_ref[0, lpos])
        vals_ref[0, lpos] = jnp.where(do_pop, -1, vals_ref[0, lpos])

        ok_ref[0, i] = (do_ins | do_pop).astype(jnp.int32)
        return jnp.where(do_ins, size + 1, nsize)

    final = jax.lax.fori_loop(0, b, body, size_ref[0])
    size_out_ref[0, 0] = final


def heap_apply(keys, vals, size, ops, opkeys, opvals, *, cap_log2: int,
               arity_log2: int = 2, interpret=None):
    """Apply a batch of heap ops in batch order.  ``keys``/``vals`` are
    (cap,) int32 planes (empty slots KEY_INF / -1); ``size`` a scalar
    int32; ``ops``/``opkeys``/``opvals`` are (B,) int32.
    ``interpret=None`` resolves via REPRO_PALLAS_INTERPRET / backend.
    Returns ``(keys, vals, new_size, out_keys, out_vals, ok)`` where
    ``out_*[i]`` carry delete-min results and ``ok[i]`` certifies op i
    applied.  Not on the engine path: v5e refuses its dynamic scalar VMEM
    stores; engines call ``heap_planes``."""
    return _heap_apply_jit(keys, vals, size, ops, opkeys, opvals,
                           cap_log2=cap_log2, arity_log2=arity_log2,
                           interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("cap_log2", "arity_log2", "interpret"))
def _heap_apply_jit(keys, vals, size, ops, opkeys, opvals, *, cap_log2: int,
                    arity_log2: int, interpret: bool):
    cap = 1 << cap_log2
    b = ops.shape[0]
    kern = functools.partial(_heap_kernel, cap_log2, arity_log2)
    call = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b), lambda i: (0, 0)),
            pl.BlockSpec((1, b), lambda i: (0, 0)),
            pl.BlockSpec((1, b), lambda i: (0, 0)),
        ] + [pl.BlockSpec((1, cap), lambda i: (0, 0))] * 2,
        out_specs=[pl.BlockSpec((1, cap), lambda i: (0, 0))] * 2
        + [pl.BlockSpec((1, b), lambda i: (0, 0))] * 3
        + [pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, cap), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((1, b), jnp.int32)] * 3
        + [jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )
    with jax.named_scope("repro.heap_apply"):
        outs = call(size.reshape(1), ops.reshape(1, b), opkeys.reshape(1, b),
                    opvals.reshape(1, b), keys.reshape(1, cap),
                    vals.reshape(1, cap))
    k, v, outk, outv, ok, nsize = outs
    return (k.reshape(cap), v.reshape(cap), nsize.reshape(())[()],
            outk.reshape(b), outv.reshape(b), ok.reshape(b).astype(bool))


# ---------------------------------------------------------------------------
# pure-jnp plane face — the shard_map/while_loop-inlinable twin of the kernel
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cap_log2", "arity_log2"))
def heap_planes(keys, vals, size, ops, opkeys, opvals, *, cap_log2: int,
                arity_log2: int = 2, rider=None, oprider=None):
    """Apply a batch of heap ops in batch order — pure jnp, no Pallas.

    Same contract and bit-identical results as ``heap_apply`` (the batch
    is the linearization order; ``OP_NOP`` lanes are inert), but expressed
    as a ``lax.scan`` over the batch with fixed-trip sift loops, so it can
    be inlined into a jitted ``lax.while_loop`` under ``shard_map`` — the
    mesh analogue of ``ring_slots.enq_planes``/``deq_planes``.  All inputs
    may be traced (``size`` and the op vectors included); only the shapes
    are static.  Returns ``(keys, vals, new_size, out_keys, out_vals,
    ok)`` with ``out_*[i]`` carrying delete-min results.

    ``rider`` is an optional second (cap,) value plane that moves in
    lockstep with ``vals`` through every sift — the span layer's
    birth-stamp plane (DESIGN.md § 7.6).  ``oprider`` supplies the rider
    value installed by INSERT lanes (scalar or (B,); ignored on pops).
    With a rider the return tuple grows to ``(..., ok, rider, out_rider)``;
    without one the op sequence — and therefore the result — is exactly
    the single-plane version's."""
    cap = 1 << cap_log2
    d = 1 << arity_log2
    max_depth = -(-cap_log2 // arity_log2) + 1
    size = jnp.asarray(size, jnp.int32)
    ops = ops.astype(jnp.int32)
    # generalize over a tuple of value planes: the heap's ordering lives
    # entirely in `keys`; every value plane just mirrors the moves
    if rider is None:
        vplanes = (vals,)
        opvals_t = (opvals.astype(jnp.int32),)
    else:
        opr = jnp.zeros_like(ops) if oprider is None else jnp.broadcast_to(
            jnp.asarray(oprider, jnp.int32), ops.shape)
        vplanes = (vals, rider)
        opvals_t = (opvals.astype(jnp.int32), opr)

    def one(carry, opkv):
        keys, vs, size = carry
        op, key, ovals = opkv

        # ---- INSERT: hole starts at `size`, parents move down ----------
        do_ins = (op == OP_INSERT) & (size < cap)

        def up(_, c):
            keys, vs, j, moving = c
            p = jnp.where(j > 0, (j - 1) >> arity_log2, 0)
            pk = keys[p]
            cond = moving & (j > 0) & (pk > key)
            jc = jnp.where(cond, j, cap)        # failed lanes drop
            keys = keys.at[jc].set(pk, mode="drop")
            vs = tuple(v.at[jc].set(v[p], mode="drop") for v in vs)
            return (keys, vs, jnp.where(cond, p, j), moving & cond)

        j0 = jnp.where(do_ins, size, 0)
        keys, vs, jf, _ = jax.lax.fori_loop(
            0, max_depth, up, (keys, vs, j0, do_ins))
        ins_at = jnp.where(do_ins, jf, cap)
        keys = keys.at[ins_at].set(key, mode="drop")
        vs = tuple(v.at[ins_at].set(ov, mode="drop")
                   for v, ov in zip(vs, ovals))

        # ---- DELETE-MIN: root out, last node sifts down into the hole --
        do_pop = (op == OP_DELMIN) & (size > 0)
        outk = jnp.where(do_pop, keys[0], KEY_INF)
        outs = tuple(jnp.where(do_pop, v[0], -1) for v in vs)
        nsize = jnp.where(do_pop, size - 1, size)
        lpos = jnp.where(do_pop & (size > 0), size - 1, 0)
        lk = keys[lpos]
        lvs = tuple(v[lpos] for v in vs)

        def down(_, c):
            keys, vs, j, moving = c
            base = (j << arity_log2) + 1

            def child(cc, acc):
                bk, bj = acc
                cj = base + cc
                in_r = cj < nsize
                ck = jnp.where(in_r, keys[jnp.where(in_r, cj, 0)], KEY_INF)
                better = ck < bk
                return (jnp.where(better, ck, bk), jnp.where(better, cj, bj))

            bk, bj = jax.lax.fori_loop(
                0, d, child, (jnp.int32(KEY_INF), jnp.int32(-1)))
            cond = moving & (bj >= 0) & (bk < lk)
            jc = jnp.where(cond, j, cap)
            bsrc = jnp.where(cond, bj, 0)
            keys = keys.at[jc].set(bk, mode="drop")
            vs = tuple(v.at[jc].set(v[bsrc], mode="drop") for v in vs)
            return (keys, vs, jnp.where(cond, bj, j), moving & cond)

        moving0 = do_pop & (nsize > 0)
        keys, vs, jf2, _ = jax.lax.fori_loop(
            0, max_depth, down, (keys, vs, jnp.int32(0), moving0))
        place = jnp.where(moving0, jf2, cap)
        keys = keys.at[place].set(lk, mode="drop")
        vs = tuple(v.at[place].set(lv, mode="drop")
                   for v, lv in zip(vs, lvs))
        # scrub the vacated tail slot so stale keys can't resurface
        scrub = jnp.where(do_pop, lpos, cap)
        keys = keys.at[scrub].set(KEY_INF, mode="drop")
        vs = tuple(v.at[scrub].set(-1, mode="drop") for v in vs)

        ok = (do_ins | do_pop).astype(jnp.int32)
        new_size = jnp.where(do_ins, size + 1, nsize)
        return (keys, vs, new_size), (outk, outs, ok)

    (keys, vplanes, size), (outk, outvs, ok) = jax.lax.scan(
        one, (keys, vplanes, size),
        (ops, opkeys.astype(jnp.int32), opvals_t))
    if rider is None:
        return keys, vplanes[0], size, outk, outvs[0], ok.astype(bool)
    return (keys, vplanes[0], size, outk, outvs[0], ok.astype(bool),
            vplanes[1], outvs[1])


def heap_pop_count(keys, vals, size, count, *, batch: int, cap_log2: int,
                   arity_log2: int = 2, rider=None):
    """Pop the ``count`` smallest (key, val) pairs through a fixed-width
    masked wave: lanes ``>= count`` are ``OP_NOP`` padding, so ``count``
    may be traced (the mesh claim schedule's per-shard share).  Returns
    the ``heap_planes`` tuple; ``ok[i] = i < min(count, size)``.  An
    optional ``rider`` plane passes through (the popped rider values land
    in the appended ``out_rider``)."""
    lane = jnp.arange(batch, dtype=jnp.int32)
    ops = jnp.where(lane < jnp.asarray(count, jnp.int32), OP_DELMIN, OP_NOP)
    pad = jnp.full((batch,), KEY_INF, jnp.int32)
    return heap_planes(keys, vals, size, ops, pad, pad,
                       cap_log2=cap_log2, arity_log2=arity_log2, rider=rider)


def heap_insert_masked(keys, vals, size, inkeys, invals, mask, *,
                       cap_log2: int, arity_log2: int = 2, rider=None,
                       oprider=None):
    """Install the masked subset of a fixed-width (key, val) wave in lane
    order (masked-out lanes are ``OP_NOP``) — the publish wave of the
    priority mesh rounds, where each shard keeps only its sprayed share of
    the gathered children.  Returns the ``heap_planes`` tuple.  An
    optional ``rider`` plane installs ``oprider`` (scalar or (B,)) on
    applied lanes — the span layer's birth stamps."""
    ops = jnp.where(mask, OP_INSERT, OP_NOP)
    return heap_planes(keys, vals, size, ops, inkeys, invals,
                       cap_log2=cap_log2, arity_log2=arity_log2,
                       rider=rider, oprider=oprider)
