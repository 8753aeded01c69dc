"""The distributed (mesh-level) bounded FIFO queue — the paper's design
carried above the chip (DESIGN.md § 2.3).

Aggregation hierarchy: lane → block (Pallas wavefaa, one counter update) →
chip → mesh (this module: one collective hands every chip the round's
compact op blocks *and* a contiguous ticket block).  The ring state (the
same four int32 field planes as ``kernels/ring_slots``) is replicated per
shard and advanced by the deterministic per-round ticket order, so every
chip holds an identical view after each round — FIFO and linearizability
hold by construction: rounds are totally ordered by the collective
schedule, and within a round tickets order operations exactly as
per-thread FAA would (Lemma III.1 applied at mesh scope).

API (pure-functional, jit/shard_map-compatible):

    state = dist_queue_init(capacity)                      # capacity → pow2
    state, granted = dist_enqueue_round(state, values, mask, axis="data")
    state, vals, ok = dist_dequeue_round(state, want, axis="data")
    state, vals, ok = dist_claim_round(state, k, batch, axis="data")

Two interchangeable application engines (bit-identical planes):

* ``engine="planes"`` (default) — the round's gathered ops are applied as
  one-shot masked scatters through the *shared* ``ring_slots.enq_planes``
  / ``deq_planes`` updates.  A round's tickets are contiguous, so chunking
  them into sub-waves of 2n consecutive tickets guarantees pairwise-
  distinct slots per sub-wave (Lemma III.1's precondition); rounds with
  ≤ 2n ops (the common case) are a single scatter.
* ``engine="scan"`` — the legacy serial reference: one op per scan step in
  ticket order (sorted by ticket *age* ``ticket - tail`` with an
  order-safe ``INT32_MAX`` sentinel for inactive lanes — sorting raw
  tickets breaks once they pass the sentinel value, and sorting with a
  mid-range sentinel interleaves masked-out lanes before live ones).

Wrap safety (wCQ-style): tail/head/tickets are *unsigned mod-2^32*
counters carried in int32.  All comparisons are wraparound differences,
slot index is a power-of-two mask, and the cycle is a logical shift — so
the queue survives ticket counters crossing 2^31 (liveness of an op is an
explicit mask, never a sign test).

Replication typing: payload exchange uses ``mesh_round_gather`` — a
single psum that is bit-exact integer gather *and* replicated-typed, so
the updated planes satisfy shard_map's varying-manual-axes checker and
callers keep ``P()`` out_specs with ``check_vma=True``.  ``dist_claim_round``
needs no collective at all: the claim schedule is a pure function of the
replicated head/tail.

Priority plane variant (DESIGN.md § 6): ``DistHeapState`` carries the
heap's key/val planes at mesh scope — *sharded* (one local heap per shard,
the k-relaxed mode) or *replicated* (every shard holds the full heap, the
strict mode), the caller's choice of shard_map specs decides which.
``priority_claim_schedule`` is ``claim_schedule``'s hint-ordered twin
(even split of the round's budget, remainder to the lowest-*key* shards
instead of the lowest indices, clamped to each shard's local size), and
``dist_priority_publish_round`` is the one-psum publish exchange: each
shard's packed ``(key | payload)`` child blocks ride next to a
``(min-hint, size)`` meta word in a single ``mesh_round_gather`` row, so
the next round's claim schedule is a pure function of replicated values —
no second collective.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..distributed.collectives import mesh_round_gather, mesh_ticket_base  # noqa: F401  (ticket base re-exported for callers)
from ..jaxcompat import pvary as _pvary
from ..kernels.compact import compact_planes
from ..kernels.heap_batch import KEY_INF
from ..kernels.ring_slots import deq_planes, enq_planes

IDX_BOT = jnp.int32(2 ** 31 - 1)
IDX_BOTC = jnp.int32(2 ** 31 - 2)
_SENTINEL = jnp.int32(2 ** 31 - 1)      # order-safe: sorts after any live rank


class DistQueueState(NamedTuple):
    """Replicated ring state (per-shard identical by construction).  Same
    field-plane layout as the chip-level ``RingState`` so both levels share
    the ``ring_slots`` plane updates."""
    cycles: jax.Array   # (2n,) int32
    safes: jax.Array    # (2n,) int32
    enqs: jax.Array     # (2n,) int32
    idxs: jax.Array     # (2n,) int32 — payload or ⊥ / ⊥_c
    tail: jax.Array     # () int32 — unsigned mod-2^32 ticket counter
    head: jax.Array     # () int32 — unsigned mod-2^32 ticket counter

    @property
    def occupancy(self):
        return self.tail - self.head    # wraparound difference


def dist_queue_init(capacity: int, *, start: int = None) -> DistQueueState:
    """Ring with logical capacity rounded up to a power of two (2n physical
    slots; power-of-two slot counts make wrapped-ticket slot indexing a
    mask).  ``start`` overrides the initial head/tail ticket (tests use it
    to start the ring near the int32 boundary); it must be a multiple of
    2n so tickets stay slot-aligned with cycle arithmetic."""
    cap = 1 << max(int(capacity) - 1, 1).bit_length()
    n2 = 2 * cap
    if start is None:
        start = n2                       # first tickets: cycle 1 over cycle-0
    if start % n2:
        raise ValueError(f"start {start} must be a multiple of 2n={n2}")
    start_u = int(start) % (2 ** 32)     # unsigned view, then signed repr
    start = jnp.int32(start_u - 2 ** 32 if start_u >= 2 ** 31 else start_u)
    # empty slots must carry the cycle *before* the start ticket's cycle
    # (wrapped): cycle_lt(init_cycle, start_cycle) has to hold or the first
    # installs are rejected as stale.
    lg = n2.bit_length() - 1
    cyc0_u = ((start_u >> lg) - 1) % (2 ** (32 - lg))
    cyc0 = jnp.int32(cyc0_u - 2 ** 32 if cyc0_u >= 2 ** 31 else cyc0_u)
    return DistQueueState(
        cycles=jnp.full((n2,), cyc0, jnp.int32),
        safes=jnp.ones((n2,), jnp.int32),
        enqs=jnp.zeros((n2,), jnp.int32),
        idxs=jnp.full((n2,), IDX_BOT),
        tail=start,
        head=start,
    )


def _nslots_log2(state: DistQueueState) -> int:
    n2 = state.cycles.shape[0]
    lg = n2.bit_length() - 1
    assert (1 << lg) == n2, "slot count must be a power of two"
    return lg


def _planes(state: DistQueueState):
    return (state.cycles, state.safes, state.enqs, state.idxs)


def _subwaves(total_ops: int, n2: int) -> int:
    """How many ≤2n-ticket sub-waves a round of ``total_ops`` needs so each
    applied wave hits pairwise-distinct slots (Lemma III.1)."""
    return -(-total_ops // n2)


def _apply_enqueue(planes, head, tickets, values, active, ranks, *,
                   nslots_log2: int, engine: str, max_rank: int = None,
                   births=None, birth_round=None):
    """Apply one round of gathered enqueue ops to the planes.  ``tickets``
    = tail + rank (wrapping); ``ranks`` ∈ [0, total) for active ops.
    ``max_rank`` is a static upper bound on active ranks (callers that cap
    the round's total, e.g. by capacity, pass it so provably-inert
    sub-waves are never emitted).  Returns (planes, ok) with ok in
    gathered op order; a span-layer stamp plane (``births`` +
    ``birth_round``, see ``ring_slots.enq_planes``) threads through every
    sub-wave and is appended when given."""
    n2 = 1 << nslots_log2
    nops = tickets.shape[0]
    if engine == "planes":
        ok = jnp.zeros((nops,), jnp.int32)
        for w in range(_subwaves(min(nops, max_rank or nops), n2)):
            wave = active & (ranks >= w * n2) & (ranks < (w + 1) * n2)
            out = enq_planes(
                *planes, tickets, values, head,
                nslots_log2=nslots_log2, idx_bot=int(IDX_BOT), active=wave,
                births=births, birth_round=birth_round)
            planes, okw = out[:4], out[4]
            if births is not None:
                births = out[5]
            ok = ok | okw
        if births is not None:
            return planes, ok, births
        return planes, ok
    if engine != "scan":
        raise ValueError(f"unknown engine {engine!r} (planes|scan)")
    order = jnp.argsort(jnp.where(active, ranks, _SENTINEL))

    def body(carry, tva):
        pl, brt = carry
        t, v, a = tva
        out = enq_planes(
            *pl, t[None], v[None], head,
            nslots_log2=nslots_log2, idx_bot=int(IDX_BOT), active=a[None],
            births=brt, birth_round=birth_round)
        return ((out[:4], out[5] if brt is not None else None), out[4][0])

    (planes, births), ok_sorted = jax.lax.scan(
        body, (planes, births),
        (tickets[order], values[order], active[order]))
    ok = ok_sorted[jnp.argsort(order)]
    if births is not None:
        return planes, ok, births
    return planes, ok


def _apply_dequeue(planes, tickets, active, ranks, *,
                   nslots_log2: int, engine: str, births=None):
    """Apply one round of gathered dequeue ops.  Returns
    (planes, vals, ok) in gathered op order; with a span-layer stamp plane
    (``births``) the consumed slots' birth rounds are appended (-1 on
    missed lanes)."""
    n2 = 1 << nslots_log2
    nops = tickets.shape[0]
    if engine == "planes":
        ok = jnp.zeros((nops,), jnp.int32)
        vals = jnp.full((nops,), -1, jnp.int32)
        bvals = None if births is None else jnp.full((nops,), -1, jnp.int32)
        for w in range(_subwaves(nops, n2)):
            wave = active & (ranks >= w * n2) & (ranks < (w + 1) * n2)
            out = deq_planes(
                *planes, tickets,
                nslots_log2=nslots_log2, idx_bot=int(IDX_BOT), active=wave,
                births=births)
            planes, v, okw = out[:4], out[4], out[5]
            ok = ok | okw
            vals = jnp.where(wave, v, vals)
            if births is not None:
                bvals = jnp.where(wave, out[6], bvals)
        if births is not None:
            return planes, vals, ok, bvals
        return planes, vals, ok
    if engine != "scan":
        raise ValueError(f"unknown engine {engine!r} (planes|scan)")
    order = jnp.argsort(jnp.where(active, ranks, _SENTINEL))

    def body(pl, ta):
        t, a = ta
        out = deq_planes(
            *pl, t[None],
            nslots_log2=nslots_log2, idx_bot=int(IDX_BOT), active=a[None],
            births=births)
        ys = (out[4][0], out[5][0])
        if births is not None:
            ys = ys + (out[6][0],)
        return out[:4], ys

    planes, ys = jax.lax.scan(body, planes, (tickets[order], active[order]))
    inv = jnp.argsort(order)
    if births is not None:
        vals_sorted, ok_sorted, b_sorted = ys
        return planes, vals_sorted[inv], ok_sorted[inv], b_sorted[inv]
    vals_sorted, ok_sorted = ys
    return planes, vals_sorted[inv], ok_sorted[inv]


def _gathered_round(values, mask, axis):
    """One-psum exchange of the round's compact blocks.  Returns flattened
    (n·B,) gathered (values, active, ranks, total): ranks are the global
    exclusive prefix ranks over the gathered mask (shard-major, in-shard
    row-major — exactly the ticket order per-shard FAA bases would give)."""
    mask_i = (mask > 0).astype(jnp.int32)
    gv, gm = mesh_round_gather((values.astype(jnp.int32), mask_i), axis)
    gv, gm = gv.reshape(-1), gm.reshape(-1)
    active = gm > 0
    ranks = jnp.cumsum(gm) - gm
    return gv, active, ranks, jnp.sum(gm)


def dist_enqueue_round(state: DistQueueState, values: jax.Array,
                       mask: jax.Array, axis: str, *,
                       engine: str = "planes"):
    """One enqueue round inside shard_map.  values/mask: (B,) local
    requests.  Returns (new_state, granted mask (B,))."""
    b = values.shape[0]
    lg = _nslots_log2(state)
    gv, active, ranks, total = _gathered_round(values, mask, axis)
    tickets = state.tail + ranks            # wraps mod 2^32 in int32
    planes, ok = _apply_enqueue(_planes(state), state.head, tickets, gv,
                                active, ranks, nslots_log2=lg, engine=engine)
    new_state = DistQueueState(*planes, tail=state.tail + total,
                               head=state.head)
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    ok_local = _pvary(ok, axis).reshape(n, b)[me]
    return new_state, (ok_local > 0) & (mask > 0)


def dist_dequeue_round(state: DistQueueState, want: jax.Array, axis: str, *,
                       engine: str = "planes"):
    """One dequeue round.  want: (B,) local request mask.  Dequeue tickets
    are issued for every request — like FAA-based TRYDEQ, requests beyond
    the occupancy burn their ticket against an empty slot (⊥-advance) and
    return ok=False.  Returns (new_state, values (B,), ok (B,))."""
    b = want.shape[0]
    lg = _nslots_log2(state)
    _, active, ranks, total = _gathered_round(want, want, axis)
    tickets = state.head + ranks
    planes, vals, ok = _apply_dequeue(_planes(state), tickets, active, ranks,
                                      nslots_log2=lg, engine=engine)
    new_state = DistQueueState(*planes, tail=state.tail,
                               head=state.head + total)
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    vals_local = _pvary(vals, axis).reshape(n, b)[me]
    ok_local = _pvary(ok, axis).reshape(n, b)[me]
    return new_state, vals_local, (ok_local > 0) & (want > 0)


def dist_publish_round(state: DistQueueState, values: jax.Array,
                       mask: jax.Array, axis: str, *, capacity: int,
                       engine: str = "planes", with_counts: bool = False,
                       births=None, birth_round=None):
    """Enqueue round with traced overflow suppression (the fused mesh
    engine's install wave): when the round's total spawn would push
    occupancy past ``capacity``, NOTHING installs, tail stays put, and
    ``over`` returns True so the driver can raise host-side at the next
    sync.  Returns (new_state, granted (B,), total, over).

    ``with_counts=True`` (the telemetry path, DESIGN.md § 7) additionally
    returns the per-shard publish counts ``(n,) int32`` — each shard's
    contribution to the gathered round, zeroed on suppression.  The counts
    are row sums of the already-gathered mask: replicated for free, no
    extra collective.

    ``births``/``birth_round`` (the span path, DESIGN.md § 7.6) stamp the
    installed slots' birth rounds; the updated stamp plane is appended to
    the return tuple.  ``birth_round`` is a replicated scalar (the mesh
    round index), so the stamps never ride the psum — the
    one-collective-per-round invariant holds with spans on.  Suppressed
    rounds stamp nothing (``active`` is already zeroed)."""
    b = values.shape[0]
    lg = _nslots_log2(state)
    gv, active, ranks, total = _gathered_round(values, mask, axis)
    over = (state.occupancy + total) > capacity
    active = active & ~over
    tickets = state.tail + ranks
    # suppression bounds active ranks by capacity: at most one live wave
    out = _apply_enqueue(_planes(state), state.head, tickets, gv,
                         active, ranks, nslots_log2=lg, engine=engine,
                         max_rank=capacity, births=births,
                         birth_round=birth_round)
    planes, ok = out[0], out[1]
    total = jnp.where(over, 0, total)
    new_state = DistQueueState(*planes, tail=state.tail + total,
                               head=state.head)
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    ok_local = _pvary(ok, axis).reshape(n, b)[me]
    granted = (ok_local > 0) & (mask > 0)
    res = (new_state, granted, total, over)
    if with_counts:
        counts = _pvary(active, axis).reshape(n, b).sum(1, dtype=jnp.int32)
        res = res + (counts,)
    if births is not None:
        res = res + (out[2],)
    return res


def _compact_grid(counts, width: int):
    """Reconstruct the gathered op grid from per-shard compact counts (the
    dense-wave rule, DESIGN.md § 4.4).  Each shard's dense block holds its
    active lanes in local rank order, so the global ranks are the local
    lane offset by the exclusive prefix sum of counts — the identical
    shard-major, in-shard row-major order the sparse gather's cumsum
    yields.  Returns flattened (n·width,) (active, ranks)."""
    counts = jnp.asarray(counts, jnp.int32)
    base = jnp.cumsum(counts) - counts
    lane = jnp.arange(width, dtype=jnp.int32)[None, :]
    act2 = lane < jnp.minimum(counts, width)[:, None]
    ranks = jnp.where(act2, base[:, None] + lane, 0)
    return act2.reshape(-1), ranks.reshape(-1)


def dist_publish_compact_round(state: DistQueueState, values: jax.Array,
                               mask: jax.Array, axis: str, *, capacity: int,
                               width: int, with_counts: bool = False,
                               births=None, birth_round=None):
    """``dist_publish_round`` under the dense-wave rule (DESIGN.md § 4.4):
    each shard ballot-compacts its (B,) sparse child block down to a
    (width,) dense prefix wave *before* the exchange, so the one psum
    carries O(width) words per shard instead of O(B) — same single
    collective, smaller payload, and the downstream scatter is
    width-bounded.  The per-shard true popcount rides a meta word; the
    global ranks are rebuilt from the exclusive prefix sum of the counts,
    which is exactly the sparse gather's cumsum order, so the installed
    (ticket, value) pairs — and hence the planes — are bit-identical to
    the sparse round's.  A shard whose spawn count exceeds ``width`` can
    only occur when the round's total exceeds ``capacity`` (width is the
    engine's capacity bound), i.e. when ``over`` suppresses the entire
    install in both paths — lane drops are unobservable.  Returns
    ``(new_state, None, total, over)`` — the per-lane granted mask does
    not survive compaction; the fused engines never read it."""
    lg = _nslots_log2(state)
    mask_i = (mask > 0).astype(jnp.int32)
    (dv,), count = compact_planes(mask_i, (values.astype(jnp.int32),),
                                  width=width)
    gv, gmeta = mesh_round_gather(
        (dv, jnp.reshape(count.astype(jnp.int32), (1,))), axis)
    counts = gmeta[:, 0]
    total = jnp.sum(counts)
    active, ranks = _compact_grid(counts, width)
    over = (state.occupancy + total) > capacity
    active = active & ~over
    tickets = state.tail + ranks
    # suppression bounds active ranks by capacity: at most one live wave
    out = _apply_enqueue(_planes(state), state.head, tickets,
                         gv.reshape(-1), active, ranks, nslots_log2=lg,
                         engine="planes", max_rank=capacity, births=births,
                         birth_round=birth_round)
    total = jnp.where(over, 0, total)
    new_state = DistQueueState(*out[0], tail=state.tail + total,
                               head=state.head)
    res = (new_state, None, total, over)
    if with_counts:
        res = res + (jnp.where(over, 0, counts),)
    if births is not None:
        res = res + (out[2],)
    return res


def claim_schedule(k, n: int, batch: int):
    """The round's cross-shard rebalancing policy: split a claim budget of
    ``k`` items evenly over ``n`` shards (remainder to the lowest shard
    indices), each shard claiming at most ``batch``.  Because the ring
    state is replicated, the schedule is a pure function of (k, n, batch):
    a shard whose own step spawned nothing still pulls its full share of
    the round's gathered compact block — work stealing degenerates to
    perfect rebalancing at mesh scope.  Returns (active (n·batch,) bool,
    ranks (n·batch,) int32) over the gathered op grid."""
    k = jnp.minimum(jnp.asarray(k, jnp.int32), n * batch)
    share, rem = k // n, k % n
    i = jnp.arange(n, dtype=jnp.int32)[:, None]
    lane = jnp.arange(batch, dtype=jnp.int32)[None, :]
    k_i = share + (i < rem)
    start_i = i * share + jnp.minimum(i, rem)
    active = lane < k_i
    ranks = start_i + lane
    return active.reshape(-1), jnp.where(active, ranks, 0).reshape(-1)


def dist_claim_round(state: DistQueueState, k, batch: int, axis: str, *,
                     engine: str = "planes", with_grid: bool = False,
                     births=None):
    """Claim ``k`` items (a replicated scalar, ≤ occupancy) spread evenly
    over the shards — ``claim_schedule`` — with NO collective: every shard
    derives the full mesh's dequeue tickets from the replicated head.
    Returns (new_state, values (batch,), ok (batch,)) — values/ok are this
    shard's slice of the schedule.

    ``with_grid=True`` (the telemetry path, DESIGN.md § 7) additionally
    returns the full gathered claim grid ``(values (n·batch,), ok
    (n·batch,))`` — computed from replicated planes/tickets, so it is
    already replicated: global per-round extrema come for free, no
    collective.

    ``births`` (the span path, DESIGN.md § 7.6) reads the consumed slots'
    birth stamps; this shard's (batch,) slice of them is appended to the
    return tuple (-1 on missed lanes).  The stamp plane itself is
    read-only at claim time."""
    lg = _nslots_log2(state)
    n = jax.lax.axis_size(axis)
    active, ranks = claim_schedule(k, n, batch)
    tickets = state.head + ranks
    out = _apply_dequeue(_planes(state), tickets, active, ranks,
                         nslots_log2=lg, engine=engine, births=births)
    planes, vals, ok = out[0], out[1], out[2]
    k = jnp.minimum(jnp.asarray(k, jnp.int32), n * batch)
    new_state = DistQueueState(*planes, tail=state.tail, head=state.head + k)
    me = jax.lax.axis_index(axis)
    vals_full = _pvary(vals, axis)
    ok_full = _pvary(ok, axis)
    vals_local = vals_full.reshape(n, batch)[me]
    ok_local = ok_full.reshape(n, batch)[me]
    res = (new_state, vals_local, ok_local > 0)
    if with_grid:
        res = res + ((vals_full, ok_full > 0),)
    if births is not None:
        res = res + (_pvary(out[3], axis).reshape(n, batch)[me],)
    return res


# ---------------------------------------------------------------------------
# priority plane variant (DESIGN.md § 6) — the mesh-level G-PQ face
# ---------------------------------------------------------------------------


class DistHeapState(NamedTuple):
    """Mesh-level heap planes, same key/val layout as ``kernels/heap_batch``
    so both levels share the ``heap_planes`` batch updates.  Unlike
    ``DistQueueState`` the planes are *not* necessarily replicated: the
    relaxed priority mesh keeps one local heap per shard (sharded specs),
    the strict mode replicates the full heap on every shard."""
    keys: jax.Array     # (cap,) int32 — KEY_INF marks empty slots
    vals: jax.Array     # (cap,) int32
    size: jax.Array     # () int32 — this copy's live node count

    @property
    def occupancy(self):
        return self.size


def dist_heap_init(capacity: int) -> DistHeapState:
    """Empty heap planes with capacity rounded up to a power of two (sift
    depths and child fans are static functions of ``cap_log2``)."""
    cap = 1 << max(int(capacity) - 1, 1).bit_length()
    return DistHeapState(
        keys=jnp.full((cap,), KEY_INF, jnp.int32),
        vals=jnp.full((cap,), -1, jnp.int32),
        size=jnp.int32(0),
    )


def priority_claim_schedule(k, n: int, batch: int, hints, sizes):
    """``claim_schedule``'s hint-ordered twin — the priority mesh round's
    cross-shard rebalancing rule.  The round's pop budget ``k`` (≤ the
    global occupancy, ≤ ``n·batch``) is split evenly over the shards with
    the remainder going to the lowest-*key* shards: shards are ranked by
    their replicated min-key ``hints`` (ties by shard index — ``argsort``
    is stable), the shard at hint-rank ``p`` receives ``k//n + (p < k%n)``,
    and each share is clamped to the shard's local ``sizes`` (an empty
    sibling cannot donate).  Empty shards carry ``KEY_INF`` hints and rank
    last, so whenever the mesh holds work at least one share is nonzero —
    the round loop always makes progress.  Everything here is a pure
    function of replicated values: like the FIFO claim, the schedule
    costs NO collective.  Returns per-shard pop counts ``(n,) int32``."""
    sizes = jnp.asarray(sizes, jnp.int32)
    k = jnp.minimum(jnp.asarray(k, jnp.int32),
                    jnp.minimum(jnp.sum(sizes), n * batch))
    share, rem = k // n, k % n
    order = jnp.argsort(jnp.asarray(hints, jnp.int32))   # stable: index ties
    pos = jnp.argsort(order).astype(jnp.int32)           # hint rank per shard
    budget = share + (pos < rem)
    return jnp.minimum(budget, jnp.minimum(sizes, batch))


def dist_priority_publish_round(ckeys: jax.Array, cvals: jax.Array,
                                mask: jax.Array, local_hint: jax.Array,
                                local_size: jax.Array, axis: str,
                                pop_meta=None, aux=None):
    """The priority mesh round's ONE collective: every shard contributes
    its compact child block as packed ``(key | payload)`` words — the key
    and payload planes are concatenated into the shard's single
    ``mesh_round_gather`` row — plus a 2-word ``(post-pop min-hint,
    post-pop size)`` meta block, and one psum hands every shard the whole
    round's children *and* the replicated per-shard hints/sizes the next
    claim schedule needs.  ``ranks`` are the global exclusive prefix ranks
    over the gathered mask (shard-major, in-shard row-major — the same
    deterministic spray order per-thread FAA would give), so child → shard
    assignment (``rank % n``) is identical everywhere.  Returns
    ``(gkeys, gvals, active, ranks, total, hints (n,), sizes (n,))`` with
    the g-arrays flattened over the gathered op grid.

    ``pop_meta=(local_min, local_max)`` (the telemetry path, DESIGN.md
    § 7) widens the meta block to 4 words so each shard's popped-key
    extrema ride the SAME psum — the one-collective-per-round invariant
    holds with telemetry on — and appends ``(pop_mins (n,), pop_maxs
    (n,))`` to the return tuple.

    ``aux`` (the split-payload path, DESIGN.md § 6) is a third child
    plane carrying per-child auxiliary words (e.g. exact distances too
    wide to pack into the payload); it rides the same psum row and the
    gathered ``gaux`` is inserted right after ``gvals``."""
    mask_i = (mask > 0).astype(jnp.int32)
    meta_words = [jnp.asarray(local_hint, jnp.int32),
                  jnp.asarray(local_size, jnp.int32)]
    if pop_meta is not None:
        meta_words += [jnp.asarray(pop_meta[0], jnp.int32),
                       jnp.asarray(pop_meta[1], jnp.int32)]
    meta = jnp.stack(meta_words)
    blocks = [ckeys.astype(jnp.int32), cvals.astype(jnp.int32)]
    if aux is not None:
        blocks.append(aux.astype(jnp.int32))
    g = mesh_round_gather(tuple(blocks) + (mask_i, meta), axis)
    gm, gmeta = g[-2].reshape(-1), g[-1]
    active = gm > 0
    ranks = jnp.cumsum(gm) - gm
    out = tuple(b.reshape(-1) for b in g[:-2])
    out = out + (active, ranks, jnp.sum(gm), gmeta[:, 0], gmeta[:, 1])
    if pop_meta is not None:
        out = out + (gmeta[:, 2], gmeta[:, 3])
    return out


def dist_priority_publish_compact_round(ckeys: jax.Array, cvals: jax.Array,
                                        mask: jax.Array,
                                        local_hint: jax.Array,
                                        local_size: jax.Array, axis: str, *,
                                        width: int, pop_meta=None, aux=None):
    """``dist_priority_publish_round`` under the dense-wave rule
    (DESIGN.md § 4.4): each shard ballot-compacts its child block (key,
    payload[, aux] planes under one mask) to ``width`` dense lanes before
    the exchange, shrinking the psum row from O(B) to O(width) words per
    plane.  The true per-shard popcount rides a third meta word and the
    global ranks are rebuilt from its exclusive prefix sum — the sparse
    gather's exact cumsum order, so child → shard assignment (``rank %
    n``) and the resulting heap evolutions are bit-identical.  A count
    above ``width`` forces the engine's overflow check (width is the
    engine's install bound), where nothing installs in either path.
    Return layout matches the sparse publish with the same ``pop_meta``
    / ``aux`` options (no per-lane granted exists in either)."""
    mask_i = (mask > 0).astype(jnp.int32)
    planes_in = [ckeys.astype(jnp.int32), cvals.astype(jnp.int32)]
    if aux is not None:
        planes_in.append(aux.astype(jnp.int32))
    dense, count = compact_planes(mask_i, tuple(planes_in), width=width)
    meta_words = [jnp.asarray(local_hint, jnp.int32),
                  jnp.asarray(local_size, jnp.int32),
                  count.astype(jnp.int32)]
    if pop_meta is not None:
        meta_words += [jnp.asarray(pop_meta[0], jnp.int32),
                       jnp.asarray(pop_meta[1], jnp.int32)]
    g = mesh_round_gather(dense + (jnp.stack(meta_words),), axis)
    gmeta = g[-1]
    counts = gmeta[:, 2]
    active, ranks = _compact_grid(counts, width)
    out = tuple(b.reshape(-1) for b in g[:-1])
    out = out + (active, ranks, jnp.sum(counts), gmeta[:, 0], gmeta[:, 1])
    if pop_meta is not None:
        out = out + (gmeta[:, 3], gmeta[:, 4])
    return out


# ---------------------------------------------------------------------------
# sharded FIFO plane (DESIGN.md § 2.3) — per-shard rings, O(ring/shards)
# ---------------------------------------------------------------------------


class DistShardedQueueState(NamedTuple):
    """Per-shard ring planes: each shard owns ONE local 2n/S-slot ring
    (the planes are ``P(axis)``-sharded; inside shard_map they are this
    shard's local (2n_l,) slices) while the (S,) head/tail ticket vectors
    stay replicated — they evolve by replicated arithmetic (the claim
    schedule and the round-robin spray are pure functions of replicated
    values), so no occupancy meta word rides the psum.  Loop-carry memory
    per shard is O(ring/shards) + O(S), versus the replicated
    ``DistQueueState``'s O(ring) — the same plane discipline
    ``DistHeapState`` uses for the relaxed priority mesh."""
    cycles: jax.Array   # (2n_l,) int32 local slice (global (S, 2n_l))
    safes: jax.Array    # (2n_l,) int32
    enqs: jax.Array     # (2n_l,) int32
    idxs: jax.Array     # (2n_l,) int32 — payload or ⊥ / ⊥_c
    tails: jax.Array    # (S,) int32 replicated — per-shard unsigned tickets
    heads: jax.Array    # (S,) int32 replicated

    @property
    def occupancy(self):
        return jnp.sum(self.tails - self.heads)  # wraparound differences


def dist_sharded_queue_init(capacity: int, shards: int
                            ) -> DistShardedQueueState:
    """Global capacity rounded up to a power of two and split evenly over
    ``shards`` local rings (shards must be a power of two dividing the
    capacity, so each local slot count stays a power of two and wrapped
    tickets keep mask indexing).  Returns the GLOBAL stacked state —
    planes (S, 2n_l) ready for ``P(axis)`` sharding — with every ring
    starting at head = tail = 2n_l (first tickets: cycle 1 over
    cycle-0 slots, as in the chip ring)."""
    if shards < 1 or shards & (shards - 1):
        raise ValueError(f"shards {shards} must be a power of two")
    cap = 1 << max(int(capacity) - 1, 1).bit_length()
    if cap < shards:
        raise ValueError(f"capacity {cap} smaller than {shards} shards")
    local = cap // shards
    n2 = 2 * local
    return DistShardedQueueState(
        cycles=jnp.zeros((shards, n2), jnp.int32),
        safes=jnp.ones((shards, n2), jnp.int32),
        enqs=jnp.zeros((shards, n2), jnp.int32),
        idxs=jnp.full((shards, n2), IDX_BOT),
        tails=jnp.full((shards,), n2, jnp.int32),
        heads=jnp.full((shards,), n2, jnp.int32),
    )


def dist_sharded_claim_round(planes, heads, tails, batch: int, axis: str, *,
                             nslots_log2: int):
    """Claim up to ``S · batch`` items from the per-shard rings with NO
    collective: the per-shard pop counts are ``priority_claim_schedule``
    over the replicated (S,) occupancies — hints are the *negated*
    occupancies, so the round's budget lands on the fullest rings first
    (Wang-style dynamic rebalancing with zero exchange).  A shard only
    ever dequeues its OWN ring (tickets ``heads[me] + lane``, one
    sub-wave — batch ≤ local capacity < 2n_l).  The schedule's per-shard
    clamp means an imbalanced mesh may claim fewer than the global budget
    this round; the remainder drains over subsequent rounds.  Returns
    ``(planes, heads, vals (batch,), ok (batch,), counts (S,))``."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    occs = tails - heads
    k = jnp.minimum(jnp.sum(occs), n * batch)
    counts = priority_claim_schedule(k, n, batch, -occs, occs)
    lane = jnp.arange(batch, dtype=jnp.int32)
    active = lane < counts[me]
    tickets = jnp.where(active, heads[me] + lane, 0)
    planes, vals, ok = _apply_dequeue(planes, tickets, active, lane,
                                      nslots_log2=nslots_log2,
                                      engine="planes")
    return planes, heads + counts, vals, ok > 0, counts


def dist_sharded_publish_round(planes, heads, tails, values, mask,
                               axis: str, *, nslots_log2: int,
                               local_capacity: int, width: int = None,
                               pop_meta=None):
    """The sharded ring's ONE collective per round: gather every shard's
    child block (sparse (B,) mask or dense-wave ``width`` lanes with a
    count meta word — DESIGN.md § 4.4), then spray children round-robin
    by global rank (``rank % S`` — global ranks are contiguous, so the
    per-shard install counts are the closed form ``total//S + (s <
    total%S)``: replicated, no occupancy word needed).  Each shard
    installs only its own slice (local ticket ``tails[me] + rank//S``,
    one sub-wave).  Overflow is whole-round: if ANY local ring would
    exceed ``local_capacity``, nothing installs anywhere and ``over``
    returns True (the fused driver raises at the next sync), exactly the
    replicated publish's suppression contract.

    ``pop_meta=(local_min, local_max)`` rides extrema words on the same
    psum (the telemetry path — local claim extrema are NOT replicated, so
    they must cross the mesh to land in the replicated trace plane;
    one-collective-per-round still holds).  Returns ``(planes, tails,
    total, over, assigned (S,)[, pop_mins (S,), pop_maxs (S,)])``."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    mask_i = (mask > 0).astype(jnp.int32)
    meta_words = []
    if pop_meta is not None:
        meta_words = [jnp.asarray(pop_meta[0], jnp.int32),
                      jnp.asarray(pop_meta[1], jnp.int32)]
    if width is None:
        blocks = (values.astype(jnp.int32), mask_i)
        if meta_words:
            g = mesh_round_gather(blocks + (jnp.stack(meta_words),), axis)
            gmeta = g[2]
        else:
            g = mesh_round_gather(blocks, axis)
            gmeta = None
        gv, gm = g[0].reshape(-1), g[1].reshape(-1)
        active = gm > 0
        ranks = jnp.cumsum(gm) - gm
        total = jnp.sum(gm)
    else:
        (dv,), count = compact_planes(mask_i, (values.astype(jnp.int32),),
                                      width=width)
        meta = jnp.stack([count.astype(jnp.int32)] + meta_words)
        g = mesh_round_gather((dv, meta), axis)
        counts_pub = g[1][:, 0]
        gmeta = g[1][:, 1:] if meta_words else None
        total = jnp.sum(counts_pub)
        active, ranks = _compact_grid(counts_pub, width)
        gv = g[0].reshape(-1)
    s_ix = jnp.arange(n, dtype=jnp.int32)
    assigned = total // n + (s_ix < total % n)
    over = jnp.any((tails - heads) + assigned > local_capacity)
    mine = active & (ranks % n == me) & ~over
    lrank = jnp.where(mine, ranks // n, 0)
    tickets = jnp.where(mine, tails[me] + lrank, 0)
    planes, _ = _apply_enqueue(planes, heads[me], tickets, gv, mine, lrank,
                               nslots_log2=nslots_log2, engine="planes",
                               max_rank=local_capacity)
    assigned = jnp.where(over, 0, assigned)
    res = (planes, tails + assigned, jnp.where(over, 0, total), over,
           assigned)
    if pop_meta is not None:
        res = res + (gmeta[:, 0], gmeta[:, 1])
    return res
