"""Batched bounded-ring slot operations as Pallas TPU kernels.

These kernels apply a *wave* of fast-path queue operations (paper Alg. 1) to
the ring state in one invocation.  The ring's packed 64-bit entry word is
represented as four parallel int32 field planes (cycle / safe / enq / idx) —
TPU-native layout: 32-bit lanes, single-writer-per-slot semantics guaranteed
by ticket uniqueness (Lemma III.1).

Exact tickets within a batch hit pairwise-distinct slots (any wave spans
< 2n tickets), so the batch needs no serial ordering at all: both kernels
are a single gather → predicate → masked scatter over the field planes,
vectorized across the whole wave.  Lanes whose predicate fails (and inactive
``ticket == -1`` lanes) are routed to an out-of-range index and dropped, so
only installing/consuming lanes touch the planes.  The same vectorized
plane updates are exposed as pure-jnp functions (``enq_planes`` /
``deq_planes``) so the round engines can inline them into a jitted
``while_loop`` without a host round-trip.

Window waves.  On a v5e the plane scatters and gathers into a 2^24-slot
ring cost about 96 ns a scatter lane and 27 ns a gather lane, dropped
lanes included (PERF.md § 5; the cause is open).  A wave whose tickets
are one contiguous run (the chip ring's dequeue ``head + [0, k)`` and enqueue ``tail +
[0, n)``) touches a window of slots that lies inside two aligned blocks
of ``Bk = window_width(width)`` slots, so ``deq_window`` / ``enq_window``
read those blocks (``dynamic_slice``), run the same predicates over the
2Bk-lane window, and write the blocks back (``dynamic_update_slice``, in
place): the same planes and results as the plane functions on the same
tickets, bit for bit, with no per-lane scatter or gather.  They need
``Bk <= n``.

Each wave function runs under a named scope (``repro.ring.enq`` /
``repro.ring.deq``) that the compiled ops carry in their ``op_name``
metadata, so a device trace tells the two waves apart.

VMEM: the Pallas kernels hold the whole ring (4 × 2n × 4 B) plus the op
batch in VMEM, in and out — 16 MiB of planes already at n = 256Ki, past
the 16 MiB scoped budget, and 512 MiB at the 2^23-entry ring a chip run
holds.  The round engines therefore run ``enq_planes``/``deq_planes``,
whose planes XLA keeps in HBM; the kernels stay as the differential
reference of the interpret-mode tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import resolve_interpret

#: Round-clock ceiling of the packed birth-stamp layout: the stamp rides
#: the upper 31 bits of the enq flag word as ``(birth << 1) | 1``, so any
#: round index >= 2^30 would wrap into the sign bit and corrupt both the
#: stamp and the flag's 0/1 semantics.  ``enq_planes`` raises at stamp
#: time (concrete birth rounds) and the engine driver clamps its chunk
#: limits to the cap (traced birth rounds) — stamps never wrap silently.
SPAN_ROUND_CAP = 1 << 30


def ticket_cycle(tickets, nslots_log2: int):
    """A ticket's ring cycle, wrap-safe: tickets are unsigned mod-2^32
    counters carried in int32, so the cycle is the *logical* right shift
    (an arithmetic shift would smear the sign bit over wrapped tickets)."""
    return jax.lax.shift_right_logical(tickets, nslots_log2)


def cycle_lt(a, b, nslots_log2: int):
    """Wrap-safe cycle comparison a < b (wCQ-style bounded-cycle
    arithmetic).  Cycles live mod 2^(32-log2(2n)), so the wraparound
    difference is computed in *cycle-modulus* space: shift it back into
    ticket space and read the int32 sign.  Valid while live cycles stay
    within half the cycle modulus of each other — guaranteed because a
    ring holds at most two live cycles at once (Lemma III.2)."""
    return ((b - a) << nslots_log2) > 0


def _install_flag(birth_round):
    """The enq-flag word an install writes: 1, or with a packed birth stamp
    ``(birth_round << 1) | 1`` (``enq_planes`` docstring).  Raises for a
    concrete round past ``SPAN_ROUND_CAP``."""
    if birth_round is None:
        return jnp.int32(1)
    if not isinstance(birth_round, jax.core.Tracer):
        if int(birth_round) >= SPAN_ROUND_CAP:
            raise ValueError(
                f"birth_round {int(birth_round)} exceeds the packed "
                f"birth-stamp cap SPAN_ROUND_CAP={SPAN_ROUND_CAP}: the "
                f"(birth << 1) | 1 layout caps the round clock at 2^30 "
                f"(use the separate births plane for longer clocks)")
    return (jnp.asarray(birth_round, jnp.int32) << 1) | 1


@jax.named_scope("repro.ring.enq")
def enq_planes(cycles, safes, enqs, idxs, tickets, values, head, *,
               nslots_log2: int, idx_bot: int, active=None,
               births=None, birth_round=None):
    """Vectorized TRYENQ install wave over the (2n,) field planes.

    ``tickets``/``values`` are (B,) int32; active tickets must hit
    pairwise-distinct slots (Lemma III.1 — true for any ticket wave
    spanning < 2n).  ``active`` masks live lanes; when ``None`` it defaults
    to ``tickets >= 0`` (the -1-sentinel convention of the chip-level
    engine).  Callers whose tickets may wrap past 2^31 (the mesh queue)
    must pass ``active`` explicitly — all ticket comparisons here are
    wraparound-difference based, so wrapped (negative) tickets behave
    correctly.  ``head`` is a scalar.  One gather per plane, one masked
    scatter per plane — no serial loop.  Returns
    (cycles, safes, enqs, idxs, ok).

    ``births``/``birth_round`` enable the span layer's birth stamps
    (DESIGN.md § 7.6), in one of two layouts:

    * **separate plane** — ``births`` is a (2n,) int32 stamp plane riding
      alongside the field planes; installing lanes reuse the already-
      computed scatter index (one extra masked scatter) and ``births`` is
      appended to the return tuple.
    * **packed flag** (``births=None``, ``birth_round`` given) — the
      install writes ``(birth_round << 1) | 1`` into the ``enqs`` flag
      plane instead of the literal 1.  The flag plane only ever carries
      0/1 semantics (the dequeue tests the low bit and nothing else reads
      it), so the stamp rides the *existing* enq scatter: zero extra ops,
      zero extra loop carry, zero extra plane copies — the layout the
      dispatch-bound chip engine uses.  Seeds installed by the unpacked
      kernel path carry ``enqs == 1`` ⇔ birth round 0, exactly the span
      seed contract; ``enqs & 1`` recovers the unpacked plane bit-exactly.
      The stamp occupies the upper 31 bits, capping the round clock at
      2^30 (``SPAN_ROUND_CAP``, enforced here for concrete rounds and by
      the engine driver for traced ones — never a silent wrap; the
      separate plane keeps full int32 range for the mesh engines).  All other
      plane updates are identical in every mode."""
    nslots = 1 << nslots_log2
    idx_botc = idx_bot - 1
    if active is None:
        active = tickets >= 0
    j = jnp.where(active, tickets & (nslots - 1), 0)
    c = jnp.where(active, ticket_cycle(tickets, nslots_log2), 0)
    e_c, e_s, e_i = cycles[j], safes[j], idxs[j]
    empty = (e_i == idx_bot) | (e_i == idx_botc)
    can = active & cycle_lt(e_c, c, nslots_log2) & empty & (
        (e_s == 1) | ((tickets - head) >= 0))
    w = jnp.where(can, j, nslots)          # failed lanes scatter out of range
    cycles = cycles.at[w].set(c, mode="drop")
    safes = safes.at[w].set(1, mode="drop")
    flag = _install_flag(birth_round if births is None else None)
    enqs = enqs.at[w].set(flag, mode="drop")
    idxs = idxs.at[w].set(values, mode="drop")
    if births is None:
        return cycles, safes, enqs, idxs, can.astype(jnp.int32)
    births = births.at[w].set(jnp.asarray(birth_round, jnp.int32),
                              mode="drop")
    return cycles, safes, enqs, idxs, can.astype(jnp.int32), births


@jax.named_scope("repro.ring.deq")
def deq_planes(cycles, safes, enqs, idxs, tickets, *,
               nslots_log2: int, idx_bot: int, active=None, births=None,
               birth_packed: bool = False):
    """Vectorized TRYDEQ consume wave (same distinct-slot precondition and
    wrap-safe comparisons as ``enq_planes``).
    Returns (cycles, safes, enqs, idxs, values, ok).

    ``births`` (the span layer's (2n,) stamp plane) adds a gather of the
    consumed slot's birth round, appended to the return tuple as a (B,)
    vector (-1 on missed lanes).  The stamp plane itself is read-only
    here — stale stamps are overwritten at the slot's next install, so no
    scrub is needed.  With the packed-flag layout (``birth_packed=True``,
    see ``enq_planes``) the birth instead rides the existing enq-flag
    gather — the hit test reads the low bit, the stamp the high bits —
    zero extra ops, and the same (B,) vector is appended."""
    nslots = 1 << nslots_log2
    idx_botc = idx_bot - 1
    if active is None:
        active = tickets >= 0
    j = jnp.where(active, tickets & (nslots - 1), 0)
    c = jnp.where(active, ticket_cycle(tickets, nslots_log2), 0)
    e_c, e_s, e_e, e_i = cycles[j], safes[j], enqs[j], idxs[j]
    empty = (e_i == idx_bot) | (e_i == idx_botc)
    flag = (e_e & 1) if birth_packed else e_e
    hit = active & (e_c == c) & (~empty) & (flag == 1)
    idxs = idxs.at[jnp.where(hit, j, nslots)].set(idx_botc, mode="drop")
    adv = active & (~hit) & empty & cycle_lt(e_c, c, nslots_log2)
    cycles = cycles.at[jnp.where(adv, j, nslots)].set(c, mode="drop")
    uns = active & (~hit) & (~empty) & cycle_lt(e_c, c, nslots_log2)
    safes = safes.at[jnp.where(uns, j, nslots)].set(0, mode="drop")
    vals = jnp.where(hit, e_i, -1)
    if birth_packed:
        bvals = jnp.where(hit, e_e >> 1, -1)
        return cycles, safes, enqs, idxs, vals, hit.astype(jnp.int32), bvals
    if births is None:
        return cycles, safes, enqs, idxs, vals, hit.astype(jnp.int32)
    bvals = jnp.where(hit, births[j], -1)
    return cycles, safes, enqs, idxs, vals, hit.astype(jnp.int32), bvals


def window_width(lanes: int) -> int:
    """Bk, the block of a window wave over ``lanes`` contiguous tickets:
    the least power of two >= ``lanes``."""
    return 1 << max(int(lanes) - 1, 0).bit_length()


def _window_read(planes, start, bk: int, nslots_log2: int):
    """The two aligned ``bk``-slot blocks that hold the slots of tickets
    ``[start, start + bk)`` mod 2n, as one (2bk,) window per plane.  Block
    starts are multiples of ``bk`` below 2n, so no ``dynamic_slice`` clamp
    can shift them.  Returns (windows, block starts, offset of ``start``
    in the window)."""
    nslots = 1 << nslots_log2
    s = start & (nslots - 1)
    b0 = s & ~(bk - 1)
    b1 = (b0 + bk) & (nslots - 1)
    wins = tuple(jnp.concatenate([jax.lax.dynamic_slice(p, (b0,), (bk,)),
                                  jax.lax.dynamic_slice(p, (b1,), (bk,))])
                 for p in planes)
    return wins, (b0, b1), s & (bk - 1)


def _window_write(plane, win, blocks):
    b0, b1 = blocks
    bk = win.shape[0] // 2
    plane = jax.lax.dynamic_update_slice(plane, win[:bk], (b0,))
    return jax.lax.dynamic_update_slice(plane, win[bk:], (b1,))


@jax.named_scope("repro.ring.deq")
def deq_window(cycles, safes, enqs, idxs, head, k, *, batch: int,
               nslots_log2: int, idx_bot: int, birth_packed: bool = False):
    """``deq_planes`` for the wave of tickets ``head + [0, k)``, ``k <=
    batch``, as block reads and writes: the wave's slots lie in two aligned
    blocks of ``Bk = window_width(batch)`` slots, which need ``2 Bk <= 2n``.
    Window lane ``q`` holds ticket ``head - off + q``; the same per-lane
    predicates run over all 2Bk lanes, active for ``off <= q < off + k``,
    and each changed plane is written back whole-block.  Tickets may wrap
    past 2^31 (no -1 sentinel).  Returns what ``deq_planes`` returns for
    ``tickets = head + arange(batch)`` masked to ``lane < k``, bit for bit:
    (cycles, safes, enqs, idxs, values, ok[, birth stamps])."""
    idx_botc = idx_bot - 1
    bk = window_width(batch)
    (w_c, w_s, w_e, w_i), blocks, off = _window_read(
        (cycles, safes, enqs, idxs), head, bk, nslots_log2)
    q = jnp.arange(2 * bk, dtype=jnp.int32)
    active = (q >= off) & (q < off + k)
    c = ticket_cycle(head - off + q, nslots_log2)
    empty = (w_i == idx_bot) | (w_i == idx_botc)
    flag = (w_e & 1) if birth_packed else w_e
    hit = active & (w_c == c) & (~empty) & (flag == 1)
    older = cycle_lt(w_c, c, nslots_log2)
    adv = active & (~hit) & empty & older
    uns = active & (~hit) & (~empty) & older
    idxs = _window_write(idxs, jnp.where(hit, idx_botc, w_i), blocks)
    cycles = _window_write(cycles, jnp.where(adv, c, w_c), blocks)
    safes = _window_write(safes, jnp.where(uns, 0, w_s), blocks)

    def lanes(x):                        # dequeue lanes are in ticket order
        return jax.lax.dynamic_slice(x, (off,), (batch,))

    out = (cycles, safes, enqs, idxs, lanes(jnp.where(hit, w_i, -1)),
           lanes(hit.astype(jnp.int32)))
    if birth_packed:
        out += (lanes(jnp.where(hit, w_e >> 1, -1)),)
    return out


@jax.named_scope("repro.ring.enq")
def enq_window(cycles, safes, enqs, idxs, tail, head, values, count, *,
               nslots_log2: int, idx_bot: int, birth_round=None):
    """``enq_planes`` for the wave of tickets ``tail + [0, count)`` whose
    values are ``values[:count]`` in ticket (rank) order, as block reads
    and writes over ``Bk = window_width(len(values))`` slots (``2 Bk <=
    2n``; see ``deq_window``).  Cycle, safe bit and flag of a window lane
    follow from its ticket ``tail - off + q``; only the payload plane takes
    ``values``, shifted to the window offset.  ``birth_round`` packs the
    stamp into the flag as in ``enq_planes``.  Returns (cycles, safes,
    enqs, idxs, ok) with ``ok`` in rank order, bit for bit what
    ``enq_planes`` returns for ``tickets = tail + arange(len(values))``
    masked to ``lane < count``."""
    idx_botc = idx_bot - 1
    width = values.shape[0]
    bk = window_width(width)
    (w_c, w_s, w_e, w_i), blocks, off = _window_read(
        (cycles, safes, enqs, idxs), tail, bk, nslots_log2)
    q = jnp.arange(2 * bk, dtype=jnp.int32)
    active = (q >= off) & (q < off + count)
    t = tail - off + q
    c = ticket_cycle(t, nslots_log2)
    empty = (w_i == idx_bot) | (w_i == idx_botc)
    can = active & cycle_lt(w_c, c, nslots_log2) & empty & (
        (w_s == 1) | ((t - head) >= 0))
    v = jax.lax.dynamic_update_slice(jnp.zeros((2 * bk,), jnp.int32),
                                     values.astype(jnp.int32), (off,))
    cycles = _window_write(cycles, jnp.where(can, c, w_c), blocks)
    safes = _window_write(safes, jnp.where(can, 1, w_s), blocks)
    enqs = _window_write(enqs, jnp.where(can, _install_flag(birth_round),
                                         w_e), blocks)
    idxs = _window_write(idxs, jnp.where(can, v, w_i), blocks)
    ok = jax.lax.dynamic_slice(can.astype(jnp.int32), (off,), (width,))
    return cycles, safes, enqs, idxs, ok


def _enq_kernel(nslots_log2, idx_bot, head_ref, tickets_ref, values_ref,
                cyc_in, saf_in, enq_in, idx_in,
                cyc_ref, saf_ref, enq_ref, idx_ref, ok_ref):
    cyc, saf, enq, idx, ok = enq_planes(
        cyc_in[...][0], saf_in[...][0], enq_in[...][0], idx_in[...][0],
        tickets_ref[...][0], values_ref[...][0], head_ref[0],
        nslots_log2=nslots_log2, idx_bot=idx_bot)
    cyc_ref[...] = cyc[None]
    saf_ref[...] = saf[None]
    enq_ref[...] = enq[None]
    idx_ref[...] = idx[None]
    ok_ref[...] = ok[None]


def _deq_kernel(nslots_log2, idx_bot, tickets_ref,
                cyc_in, saf_in, enq_in, idx_in,
                cyc_ref, saf_ref, enq_ref, idx_ref, val_ref, ok_ref):
    cyc, saf, enq, idx, vals, ok = deq_planes(
        cyc_in[...][0], saf_in[...][0], enq_in[...][0], idx_in[...][0],
        tickets_ref[...][0], nslots_log2=nslots_log2, idx_bot=idx_bot)
    cyc_ref[...] = cyc[None]
    saf_ref[...] = saf[None]
    enq_ref[...] = enq[None]
    idx_ref[...] = idx[None]
    val_ref[...] = vals[None]
    ok_ref[...] = ok[None]


@functools.partial(jax.jit,
                   static_argnames=("nslots_log2", "idx_bot", "interpret"))
def _ring_enqueue_jit(cycles, safes, enqs, idxs, tickets, values, head, *,
                      nslots_log2: int, idx_bot: int, interpret: bool):
    nslots = 1 << nslots_log2
    b = tickets.shape[0]
    kern = functools.partial(_enq_kernel, nslots_log2, idx_bot)
    call = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b), lambda i: (0, 0)),
            pl.BlockSpec((1, b), lambda i: (0, 0)),
        ] + [pl.BlockSpec((1, nslots), lambda i: (0, 0))] * 4,
        out_specs=[pl.BlockSpec((1, nslots), lambda i: (0, 0))] * 4
        + [pl.BlockSpec((1, b), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, nslots), jnp.int32)] * 4
        + [jax.ShapeDtypeStruct((1, b), jnp.int32)],
        interpret=interpret,
    )
    with jax.named_scope("repro.ring_enqueue"):
        outs = call(head.reshape(1), tickets.reshape(1, b),
                    values.reshape(1, b),
                    cycles.reshape(1, nslots), safes.reshape(1, nslots),
                    enqs.reshape(1, nslots), idxs.reshape(1, nslots))
    cyc, saf, enq, idx, ok = outs
    return (cyc.reshape(nslots), saf.reshape(nslots), enq.reshape(nslots),
            idx.reshape(nslots), ok.reshape(b).astype(bool))


def ring_enqueue(cycles, safes, enqs, idxs, tickets, values, head, *,
                 nslots_log2: int, idx_bot: int, interpret=None):
    """Apply a wave of TRYENQ installs (one masked scatter).  All field
    arrays are (2n,) int32; tickets/values are (B,) int32 (ticket -1 =
    inactive).  ``interpret=None`` resolves via REPRO_PALLAS_INTERPRET /
    backend.  Returns (cycles, safes, enqs, idxs, ok).  Not on the engine
    path: v5e's Mosaic lowering refuses its whole-ring gather ("Only 2D
    gather is supported"); engines call ``enq_planes``."""
    return _ring_enqueue_jit(cycles, safes, enqs, idxs, tickets, values,
                             head, nslots_log2=nslots_log2, idx_bot=idx_bot,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("nslots_log2", "idx_bot", "interpret"))
def _ring_dequeue_jit(cycles, safes, enqs, idxs, tickets, *,
                      nslots_log2: int, idx_bot: int, interpret: bool):
    nslots = 1 << nslots_log2
    b = tickets.shape[0]
    kern = functools.partial(_deq_kernel, nslots_log2, idx_bot)
    call = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, b), lambda i: (0, 0))]
        + [pl.BlockSpec((1, nslots), lambda i: (0, 0))] * 4,
        out_specs=[pl.BlockSpec((1, nslots), lambda i: (0, 0))] * 4
        + [pl.BlockSpec((1, b), lambda i: (0, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, nslots), jnp.int32)] * 4
        + [jax.ShapeDtypeStruct((1, b), jnp.int32)] * 2,
        interpret=interpret,
    )
    with jax.named_scope("repro.ring_dequeue"):
        outs = call(tickets.reshape(1, b),
                    cycles.reshape(1, nslots), safes.reshape(1, nslots),
                    enqs.reshape(1, nslots), idxs.reshape(1, nslots))
    cyc, saf, enq, idx, val, ok = outs
    return (cyc.reshape(nslots), saf.reshape(nslots), enq.reshape(nslots),
            idx.reshape(nslots), val.reshape(b), ok.reshape(b).astype(bool))


def ring_dequeue(cycles, safes, enqs, idxs, tickets, *,
                 nslots_log2: int, idx_bot: int, interpret=None):
    """Apply a wave of TRYDEQ consumes (one masked scatter).  Returns
    (cycles, safes, enqs, idxs, values, ok).  Not on the engine path:
    v5e's Mosaic lowering refuses its whole-ring gather; engines call
    ``deq_planes``."""
    return _ring_dequeue_jit(cycles, safes, enqs, idxs, tickets,
                             nslots_log2=nslots_log2, idx_bot=idx_bot,
                             interpret=resolve_interpret(interpret))
