"""Chip-local fused round engines (DESIGN.md § 4.3) as configurations of
the engine core (DESIGN.md § 4.8).

The legacy round loop (``rounds.py``) pays a host↔device round-trip per
round: head/tail live as host ints, tickets are ``np.arange`` math, every
enqueue chunk is its own ``pallas_call`` dispatch, and each round blocks on
an ``ok`` readback.  The fused engines run the whole dequeue → step →
ticket → enqueue cycle inside ONE jitted ``lax.while_loop``
(``enginecore.fused_loop``):

* head/tail (ring) and size (heap) are device scalars in the loop carry;
* the dequeue wave consumes the tickets ``head + [0, k)`` in one
  vectorized update;
* child tickets come from the ``wavefaa`` kernel over the spawn mask — the
  in-loop leader-FAA of paper Alg. 1 — instead of host ticket math;
* the enqueue wave installs ALL children in one vectorized update (the
  legacy path chunks them into ``batch``-sized dispatches);
* the host syncs only at quiescence, or every ``sync_every`` rounds when
  the caller wants a stats heartbeat.

Kernel faces: ``wavefaa`` is the one Pallas kernel in a round.  The ring,
heap and compaction waves run as pure-jnp plane functions on every
backend — XLA places the planes in HBM, where the Pallas twins kept the
whole ring or heap in VMEM and the v5e compiler refuses them.  Both ring
waves are contiguous ticket runs, so ``RingEngine`` runs them as window
waves (``deq_window``/``enq_window``: two aligned block reads and writes
a plane, no per-lane scatter), the children first put in ticket order by
``_rank_order`` (a compacted dense wave already is), and ``wavefaa``'s new
counter giving their count.  The windows always fit: a dequeue batch is
at most the capacity, and of a child wave only the first ``capacity``
children in rank order can ever install, so the enqueue window takes at
most that many.  ``stats["ring_window_waves"]`` counts the window waves,
two a round.

Overflow and ``max_rounds`` truncation cannot raise from traced code, so
the loop carries an overflow flag, exits early, and the host driver raises
``RuntimeError`` at the next sync — callers see the same errors as the
legacy path, one sync later.

Bit-determinism: within a round the fused engine issues exactly the
tickets the legacy loop issues (wavefaa ranks = row-major compaction
order, Lemma III.1), applies them through plane updates that are
bit-identical to the legacy loop's scatter waves, and calls the same
jitted ``step_fn`` on the same operands — so
acc, field planes, head/tail, and stats counters are bit-identical to the
legacy loop (tests assert this on BFS, raytrace, and tree workloads).
Each engine here contributes only its ``_round`` body and plane
registrations; the loop carry, chunk driver, and obs-plane lifecycle live
in ``enginecore``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.compact import compact_planes, compact_width
from ..kernels.heap_batch import (KEY_INF as HEAP_KEY_INF, OP_DELMIN,
                                  OP_INSERT, OP_NOP, heap_planes)
from ..kernels.pallas_env import resolve_interpret
from ..kernels.ring_slots import (deq_planes, deq_window, enq_planes,
                                  enq_window)
from ..kernels.wavefaa import LANES, wavefaa
from ..obs.spans import Spans, span_record, span_tick
from ..obs.trace import Telemetry, masked_min_max
from .enginecore import EngineCore, _sds, deprecated_engine

IDX_BOT = 2 ** 31 - 1           # ⊥ (⊥_c = IDX_BOT - 1); payloads must be smaller

# host-callable plane waves (seeding, the legacy per-round loop)
enq_wave = jax.jit(enq_planes, static_argnames=("nslots_log2", "idx_bot"))
deq_wave = jax.jit(deq_planes, static_argnames=("nslots_log2", "idx_bot"))


class RingState(NamedTuple):
    """Field planes of the 2n-slot ring plus host-side head/tail tickets."""
    cycles: jax.Array
    safes: jax.Array
    enqs: jax.Array
    idxs: jax.Array
    head: int
    tail: int

    @property
    def occupancy(self) -> int:
        return self.tail - self.head


def ring_init(capacity_log2: int) -> RingState:
    """Ring with logical capacity 2^capacity_log2 (2n physical slots).
    Head = Tail = 2n, so first tickets carry cycle 1 over cycle-0 slots."""
    nslots = 2 << capacity_log2
    return RingState(
        cycles=jnp.zeros((nslots,), jnp.int32),
        safes=jnp.ones((nslots,), jnp.int32),
        enqs=jnp.zeros((nslots,), jnp.int32),
        idxs=jnp.full((nslots,), IDX_BOT, jnp.int32),
        head=nslots, tail=nslots,
    )


class HeapState(NamedTuple):
    """Field planes of the device heap plus the host-side size."""
    keys: jax.Array
    vals: jax.Array
    size: int

    @property
    def occupancy(self) -> int:
        return self.size


def heap_init(capacity_log2: int) -> HeapState:
    cap = 1 << capacity_log2
    return HeapState(
        keys=jnp.full((cap,), HEAP_KEY_INF, jnp.int32),
        vals=jnp.full((cap,), -1, jnp.int32),
        size=0,
    )


# StepFn: (acc, vals (B,), valid (B,)) -> (acc, child_vals (B,F), child_mask (B,F))
StepFn = Callable[[Any, jax.Array, jax.Array], Tuple[Any, jax.Array, jax.Array]]

# PriorityStepFn: (acc, keys (B,), vals (B,), valid (B,))
#   -> (acc, child_keys (B,F), child_vals (B,F), child_mask (B,F))
PriorityStepFn = Callable[
    [Any, jax.Array, jax.Array, jax.Array],
    Tuple[Any, jax.Array, jax.Array, jax.Array]]


def _pad_lanes(mask: jax.Array) -> jax.Array:
    """Pad a flat (N,) int32 spawn mask up to a LANES multiple for wavefaa."""
    n = mask.shape[0]
    npad = -(-n // LANES) * LANES
    if npad == n:
        return mask
    return jnp.zeros((npad,), jnp.int32).at[:n].set(mask)


def _rank_order(mask: jax.Array, vals: jax.Array) -> jax.Array:
    """``vals`` with the lanes ``mask`` keeps first, in lane order — the
    row-major ranks ``wavefaa`` gives their tickets — by a stable sort on
    the inverted mask.  The lanes past the popcount are left over."""
    _, out = jax.lax.sort((jnp.where(mask, 0, 1).astype(jnp.int32), vals),
                          num_keys=1, is_stable=True)
    return out


class RingEngine(EngineCore):
    """The FIFO megaround configuration: chip ring planes + device
    head/tail scalars under the core's fused loop.  Same contract as the
    legacy ``RoundRunner.run`` (exact tickets, row-major child order,
    quiescence), with host sync only at quiescence or every
    ``sync_every`` rounds (0 = quiescence only)."""

    def __init__(self, step_fn: StepFn, *, capacity_log2: int = 10,
                 batch: int = 64, interpret=None, sync_every: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None) -> None:
        self.step_fn = jax.jit(step_fn)
        self.capacity_log2 = capacity_log2
        self.nslots_log2 = capacity_log2 + 1
        self.capacity = 1 << capacity_log2
        self.batch = batch
        if batch > self.capacity:
            raise ValueError(f"batch {batch} exceeds ring capacity "
                             f"{self.capacity}")
        self.interpret = resolve_interpret(interpret)
        self.sync_every = sync_every
        self.telemetry = telemetry
        self.spans = spans
        self.compact = compact
        self._reset()
        nslots = 2 << capacity_log2
        self.registry.register("ring", (_sds((nslots,)),) * 4
                               + (_sds(()), _sds(())))    # planes + head/tail
        # births stays None: FIFO stamps pack into the enq-flag plane
        self._register_obs_planes()
        self._megaround = jax.jit(self._megaround_impl)

    @staticmethod
    def _occ_of(q):
        return q.tail - q.head

    def _round(self, st, acc, tel=False, sp=None, births=None):
        batch, capacity = self.batch, self.capacity
        nslots_log2, interp = self.nslots_log2, self.interpret
        sps = sp is not None
        cyc, saf, enq, idx, head, tail = st
        k = jnp.minimum(jnp.int32(batch), tail - head)
        # with spans the dequeue runs in packed-flag mode: the birth stamp
        # lives in the high bits of the enq-flag plane, so it rides the
        # flag read/write the round already pays for — zero extra
        # ops, zero extra carry (measured in DESIGN.md § 7.6)
        deq = deq_window(cyc, saf, enq, idx, head, k, batch=batch,
                         nslots_log2=nslots_log2, idx_bot=IDX_BOT,
                         birth_packed=sps)
        cyc, saf, enq, idx, vals = deq[:5]
        ok = deq[5].astype(bool)
        head = head + k
        with jax.named_scope("repro.step"):
            acc, cvals, cmask = self.step_fn(acc, vals, ok)
        cm = jnp.broadcast_to(cmask.astype(bool), cvals.shape).reshape(-1)
        cv = cvals.reshape(-1).astype(jnp.int32)
        # dense-wave rule (DESIGN.md § 4.4): compact the sparse child
        # wave down to the capacity bound before installing — the
        # decision is static (trace-time) so exactly one path compiles
        wdth = compact_width(cv.shape[0], capacity, self.compact)
        if wdth is None:
            # in-loop leader FAA: the child count from the spawn-mask
            # ballot; its tickets are the run tail + rank, so the window
            # takes the children in rank order, and no more than the
            # capacity, as past it none installs (``over``)
            _, newctr = wavefaa(_pad_lanes(cm.astype(jnp.int32)),
                                jnp.reshape(tail, (1,)), interpret=interp)
            n_child = newctr[0] - tail
            with jax.named_scope("repro.ring.enq"):
                cv = _rank_order(cm, cv)[:capacity]
        else:
            # compaction subsumes the ballot: the dense wave IS the
            # children in wavefaa rank order, the contiguous tickets
            # tail + [0, n_child)
            with jax.named_scope("repro.ring.enq"):
                (cv,), n_child = compact_planes(cm.astype(jnp.int32), (cv,),
                                                width=wdth)
        over = (tail + n_child - head) > capacity
        total = jnp.where(over, 0, n_child)
        cyc, saf, enq, idx, _ = enq_window(
            cyc, saf, enq, idx, tail, head, cv, total,
            nslots_log2=nslots_log2, idx_bot=IDX_BOT,
            birth_round=sp.round if sps else None)
        tail = tail + total
        telinfo = None
        if tel:
            mn, mx = masked_min_max(vals, ok)      # FIFO: payload extrema
            telinfo = (k, total, tail - head, mn, mx)
        if sps:
            cls = self._span_cls(vals, jnp.zeros_like(vals))
            sp = span_record(sp, cls, sp.round - deq[6], ok, vals)
            sp = span_tick(sp)
        return (RingState(cyc, saf, enq, idx, head, tail), acc, k, total,
                over, telinfo, sp, births)

    def _seed(self, st: RingState, initial: np.ndarray) -> RingState:
        n = len(initial)
        if n > self.capacity:
            raise RuntimeError(
                f"ring overflow: {n} seed values exceed capacity "
                f"{self.capacity} (raise capacity_log2)")
        if n == 0:
            return st
        tickets = jnp.asarray(st.tail + np.arange(n, dtype=np.int64),
                              jnp.int32)
        cyc, saf, enq, idx, ok = enq_wave(
            st.cycles, st.safes, st.enqs, st.idxs, tickets,
            jnp.asarray(initial), jnp.asarray(st.head, jnp.int32),
            nslots_log2=self.nslots_log2, idx_bot=IDX_BOT)
        assert bool(ok.all()), "exact tickets cannot miss"
        return RingState(cyc, saf, enq, idx, st.head, st.tail + n)

    def run(self, initial: np.ndarray, acc: Any = None,
            max_rounds: int = 10_000) -> Tuple[Any, RingState]:
        """Seed the ring and run megarounds to quiescence.  Sync contract:
        the host blocks exactly once per ``sync_every`` chunk (once total
        when ``sync_every=0``) on the occupancy readback; ``stats`` and
        ``sync_log`` are populated at each sync.  Determinism: the run is
        bit-deterministic — identical tickets, planes, acc, and stats to
        the legacy per-round engine.  Raises ``RuntimeError`` on ring
        overflow or ``max_rounds`` truncation (at the sync *after* the
        flagged round, so stats reflect the partial run).  Returns
        ``(acc, final RingState)``."""
        self._reset()
        with jax.profiler.TraceAnnotation("repro.seed"):
            st = self._seed(ring_init(self.capacity_log2),
                            np.asarray(initial, np.int32).reshape(-1))
            acc = jax.tree_util.tree_map(jnp.asarray, acc)
            q = RingState(st.cycles, st.safes, st.enqs, st.idxs,
                          jnp.int32(st.head), jnp.int32(st.tail))
            state = [q, acc, jnp.int32(0), jnp.int32(0),  # processed/spawned
                     jnp.int32(st.tail - st.head)]        # max_occ
            # obs state: [TracePlane, SpanPlane, births] — None slots are
            # empty pytrees, so the all-None call is the exact unspanned
            # graph.  The FIFO ring keeps births=None: its stamps pack into
            # the enq-flag plane (seeds installed by the kernel carry flag
            # 1 ⇔ birth 0)
            ext = [self._tel_init(), self._span_init(), None]
        self._run_chunks(state, ext, lambda q: int(q.tail - q.head),
                         "ring", max_rounds)
        self.stats["ring_window_waves"] = 2 * self.stats["rounds"]
        q, acc = state[0], state[1]
        planes = (q.cycles, q.safes, q.enqs, q.idxs)
        if self.spans is not None:
            # strip packed birth stamps: the enq-flag plane is bit-identical
            # to the unspanned run's once reduced back to its low bit
            planes = (planes[0], planes[1], planes[2] & 1, planes[3])
        return acc, RingState(*planes, int(q.head), int(q.tail))


class HeapEngine(EngineCore):
    """``RingEngine``'s priority configuration: chains ``heap_planes`` pop
    and insert batches under the core's fused loop with the heap size as a
    device scalar.  The pad/op vectors are loop-invariant constants (hoisted
    by XLA), and children insert as one masked batch in row-major order —
    identical heap evolution to the legacy chunked inserts."""

    def __init__(self, step_fn: PriorityStepFn, *, capacity_log2: int = 10,
                 batch: int = 64, arity_log2: int = 2, sync_every: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None) -> None:
        self.step_fn = jax.jit(step_fn)
        self.capacity_log2 = capacity_log2
        self.capacity = 1 << capacity_log2
        self.batch = batch
        if batch > self.capacity:
            raise ValueError(f"batch {batch} exceeds heap capacity "
                             f"{self.capacity}")
        self.arity_log2 = arity_log2
        self.sync_every = sync_every
        self.telemetry = telemetry
        self.spans = spans
        self.compact = compact
        self._reset()
        cap = self.capacity
        self.registry.register("heap", (_sds((cap,)), _sds((cap,)),
                                        _sds(())))       # keys/vals + size
        self._register_obs_planes(births_shape=(cap,))
        self._megaround = jax.jit(self._megaround_impl)

    @staticmethod
    def _occ_of(q):
        return q.size

    def _round(self, st, acc, tel=False, sp=None, births=None):
        batch, capacity = self.batch, self.capacity
        cap_log2, arity_log2 = self.capacity_log2, self.arity_log2
        sps = sp is not None
        lane = jnp.arange(batch, dtype=jnp.int32)
        pad = jnp.full((batch,), HEAP_KEY_INF, jnp.int32)
        keys, vals, size = st
        k = jnp.minimum(jnp.int32(batch), size)
        pop_ops = jnp.where(lane < k, OP_DELMIN, OP_NOP)
        # with spans the births plane rides every sift as the rider plane
        with jax.named_scope("repro.heap.pop"):
            pop = heap_planes(keys, vals, size, pop_ops, pad, pad,
                              cap_log2=cap_log2, arity_log2=arity_log2,
                              rider=births)
        keys, vals, size, outk, outv, ok = pop[:6]
        with jax.named_scope("repro.step"):
            acc, ckeys, cvals, cmask = self.step_fn(acc, outk, outv, ok)
        cm = jnp.broadcast_to(cmask.astype(bool), ckeys.shape).reshape(-1)
        ckf = ckeys.reshape(-1).astype(jnp.int32)
        cvf = cvals.reshape(-1).astype(jnp.int32)
        # dense-wave rule (DESIGN.md § 4.4): compact before the insert
        # batch — the dense wave preserves row-major lane order, so the
        # masked insert sequence (hence the heap evolution) is
        # bit-identical to the sparse one
        wdth = compact_width(ckf.shape[0], capacity, self.compact)
        if wdth is None:
            n_child = cm.sum(dtype=jnp.int32)
            over = size + n_child > capacity
            ins_ops = jnp.where(cm & ~over, OP_INSERT, OP_NOP)
        else:
            with jax.named_scope("repro.heap.insert"):
                (ckf, cvf), n_child = compact_planes(
                    cm.astype(jnp.int32), (ckf, cvf), width=wdth)
            over = size + n_child > capacity
            lane_w = jnp.arange(wdth, dtype=jnp.int32)
            ins_ops = jnp.where((lane_w < n_child) & ~over,
                                OP_INSERT, OP_NOP)
        with jax.named_scope("repro.heap.insert"):
            ins = heap_planes(keys, vals, size, ins_ops, ckf, cvf,
                              cap_log2=cap_log2, arity_log2=arity_log2,
                              rider=pop[6] if sps else None,
                              oprider=sp.round if sps else None)
        keys, vals, size = ins[:3]
        total = jnp.where(over, 0, n_child)
        telinfo = None
        if tel:
            mn, mx = masked_min_max(outk, ok)      # popped-key extrema
            telinfo = (k, total, size, mn, mx)
        if sps:
            births = ins[6]
            cls = self._span_cls(outk, jnp.zeros_like(outk))
            sp = span_record(sp, cls, sp.round - pop[7], ok, outv)
            sp = span_tick(sp)
        return (HeapState(keys, vals, size), acc, k, total, over, telinfo,
                sp, births)

    def _seed(self, st: HeapState, ik: np.ndarray,
              iv: np.ndarray) -> HeapState:
        n = len(ik)
        if st.size + n > self.capacity:
            raise RuntimeError(
                f"heap overflow: {n} seed values exceed capacity "
                f"{self.capacity} (raise capacity_log2)")
        if n == 0:
            return st
        ops = jnp.full((n,), OP_INSERT, jnp.int32)
        keys, vals, size, _, _, ok = heap_planes(
            st.keys, st.vals, jnp.asarray(st.size, jnp.int32), ops,
            jnp.asarray(ik), jnp.asarray(iv), cap_log2=self.capacity_log2,
            arity_log2=self.arity_log2)
        assert bool(ok.all()), "capacity was checked: inserts cannot miss"
        return HeapState(keys, vals, int(size))

    def run(self, initial_keys: np.ndarray, initial_vals: np.ndarray,
            acc: Any = None, max_rounds: int = 10_000
            ) -> Tuple[Any, HeapState]:
        """Seed the heap and run priority megarounds to quiescence.  Same
        sync/determinism contract as ``RingEngine.run`` (one host sync
        per chunk, bit-identical to the legacy engine, RuntimeError on
        heap overflow/truncation at the next sync), with pops in exact
        min-key order within each round.  Returns ``(acc, HeapState)``."""
        self._reset()
        ik = np.asarray(initial_keys, np.int32).reshape(-1)
        iv = np.asarray(initial_vals, np.int32).reshape(-1)
        assert ik.shape == iv.shape
        with jax.profiler.TraceAnnotation("repro.seed"):
            st = self._seed(heap_init(self.capacity_log2), ik, iv)
            acc = jax.tree_util.tree_map(jnp.asarray, acc)
            q = HeapState(st.keys, st.vals, jnp.asarray(st.size, jnp.int32))
            state = [q, acc, jnp.int32(0), jnp.int32(0),  # processed/spawned
                     jnp.int32(st.size)]                  # max_occ
            ext = [self._tel_init(), self._span_init(),
                   self._births_init((self.capacity,))]
        self._run_chunks(state, ext, lambda q: int(q.size),
                         "heap", max_rounds)
        q = state[0]
        return state[1], HeapState(q.keys, q.vals, int(q.size))


@deprecated_engine("RingEngine")
class FusedRounds(RingEngine):
    """Deprecated alias of :class:`RingEngine` (same constructor and run
    contract; emits ``DeprecationWarning``)."""


@deprecated_engine("HeapEngine")
class FusedPriorityRounds(HeapEngine):
    """Deprecated alias of :class:`HeapEngine` (same constructor and run
    contract; emits ``DeprecationWarning``)."""
