"""Round-based deterministic task loop on the device ring (DESIGN.md § 4.3).

The sim face (`executor.py`) explores adversarial interleavings; this face is
the *device* execution model: task scheduling advances in jitted rounds, and
within a round every queue operation is ordered by ticket — the batched
analogue of Lemma III.1, with no nondeterminism left.  One round is

    dequeue a batch of task values from the ring (``deq_planes``),
    run the user's jitted step function on the batch,
    enqueue the children it emits (``enq_planes``) in row-major order.

Two execution engines share this contract:

* **fused** (default) — ``fusedrounds.RingEngine``: the whole round cycle
  runs on device inside one jitted ``lax.while_loop`` with head/tail as
  device scalars and ``wavefaa`` as the in-loop child-ticket source; the
  host syncs only at quiescence (or every ``sync_every`` rounds).
* **legacy** (``fused=False``) — one host-driven round per iteration:
  head/tail as host ints, exact ``np.arange`` tickets, one jitted
  dispatch per op wave.  Slower (every round is a host sync) but each
  round is a separate, inspectable step — keep it for
  adversarial/step-debug use.

Both engines are bit-identical (same acc, same planes, same head/tail —
asserted by tests) and raise ``RuntimeError`` on ring/heap overflow and on
``max_rounds`` truncation, so a non-drained return is impossible to
mistake for quiescence.

At mesh scope the same round structure runs on ``core.distqueue``:
``mesh_task_round`` composes one enqueue round and one dequeue round inside
shard_map — each chip contributes its spawn/claim masks, one collective
hands out the whole mesh's tickets and compact blocks (DESIGN.md § 2.3).
``runtime/meshrounds.py: MeshRoundRunner`` fuses that loop device-resident
(host sync only at global quiescence), exactly as this module's fused
engine does at chip scope.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.distqueue import dist_dequeue_round, dist_enqueue_round
from ..kernels.heap_batch import KEY_INF as HEAP_KEY_INF, heap_planes
from .enginecore import register_engine
from .fusedrounds import (IDX_BOT, HeapEngine, HeapState, PriorityStepFn,
                          RingEngine, RingState, StepFn, deq_wave, enq_wave,
                          heap_init, ring_init)

__all__ = [
    "IDX_BOT", "HeapState", "PriorityRoundRunner", "PriorityStepFn",
    "RingState", "RoundRunner", "StepFn", "heap_init", "mesh_task_round",
    "ring_init",
]


class RoundRunner:
    """Drives ``step_fn`` to quiescence through the device ring.

    ``fused=True`` (default) delegates to the device-resident megaround
    loop; ``fused=False`` keeps the legacy host-driven round loop.  Both
    populate ``stats`` with rounds / processed / spawned / max_occupancy /
    drained / host_syncs and raise on overflow or truncation."""

    def __init__(self, step_fn: StepFn, *, capacity_log2: int = 10,
                 batch: int = 64, interpret=None, fused: bool = True,
                 sync_every: int = 0, telemetry=None, spans=None,
                 compact=None) -> None:
        self.step_fn = jax.jit(step_fn)
        self.capacity_log2 = capacity_log2
        self.nslots_log2 = capacity_log2 + 1
        self.capacity = 1 << capacity_log2
        self.batch = batch
        self.fused = fused
        self.telemetry = telemetry
        self.spans = spans
        self.stats: Dict[str, int] = {}
        self.sync_log: List[Dict[str, int]] = []
        if telemetry is not None and not fused:
            raise ValueError("trace planes are in-loop state: telemetry "
                             "needs the fused engine (fused=True)")
        if spans is not None and not fused:
            raise ValueError("span planes are in-loop state: spans needs "
                             "the fused engine (fused=True)")
        if fused:
            self._engine = RingEngine(
                step_fn, capacity_log2=capacity_log2, batch=batch,
                interpret=interpret, sync_every=sync_every,
                telemetry=telemetry, spans=spans, compact=compact)
        else:
            self._engine = None
            # legacy-path op buffers, reused across rounds (safe because
            # jnp.asarray copies and every kernel call syncs on its ok)
            self._enq_t = np.empty(batch, np.int32)
            self._enq_v = np.empty(batch, np.int32)
            self._deq_t = np.empty(batch, np.int32)

    def _enq_chunk(self, st: RingState, vals: np.ndarray) -> RingState:
        k = len(vals)
        assert k <= self.batch
        if st.occupancy + k > self.capacity:
            raise RuntimeError(
                f"ring overflow: occupancy {st.occupancy} + {k} children "
                f"exceeds capacity {self.capacity} (raise capacity_log2 or "
                f"lower the fanout)")
        self._enq_t.fill(-1)
        self._enq_t[:k] = st.tail + np.arange(k, dtype=np.int32)
        self._enq_v.fill(-1)
        self._enq_v[:k] = vals
        cyc, saf, enq, idx, ok = enq_wave(
            st.cycles, st.safes, st.enqs, st.idxs,
            jnp.asarray(self._enq_t), jnp.asarray(self._enq_v),
            jnp.asarray(st.head, jnp.int32),
            nslots_log2=self.nslots_log2, idx_bot=IDX_BOT)
        self._host_syncs += 1
        assert bool(ok[:k].all()), "exact tickets cannot miss"
        return RingState(cyc, saf, enq, idx, st.head, st.tail + k)

    def run(self, initial: np.ndarray, acc: Any = None,
            max_rounds: int = 10_000) -> Tuple[Any, RingState]:
        """Seed the ring with ``initial`` task values, run rounds until the
        ring drains.  Returns (acc, final ring state); raises RuntimeError
        if ``max_rounds`` is hit before quiescence."""
        if self._engine is not None:
            try:
                return self._engine.run(initial, acc, max_rounds)
            finally:
                self.stats = dict(self._engine.stats, fused=1)
                self.sync_log = self._engine.sync_log
        self.stats = {}
        self.sync_log = []
        self._host_syncs = 0
        st = ring_init(self.capacity_log2)
        initial = np.asarray(initial, np.int32)
        for i in range(0, len(initial), self.batch):
            st = self._enq_chunk(st, initial[i:i + self.batch])
        rounds = processed = spawned = 0
        max_occ = st.occupancy
        while st.occupancy > 0 and rounds < max_rounds:
            k = min(self.batch, st.occupancy)
            self._deq_t.fill(-1)
            self._deq_t[:k] = st.head + np.arange(k, dtype=np.int32)
            cyc, saf, enq, idx, vals, ok = deq_wave(
                st.cycles, st.safes, st.enqs, st.idxs,
                jnp.asarray(self._deq_t),
                nslots_log2=self.nslots_log2, idx_bot=IDX_BOT)
            ok = ok.astype(bool)
            self._host_syncs += 1
            assert bool(ok[:k].all()), "exact tickets cannot miss"
            st = RingState(cyc, saf, enq, idx, st.head + k, st.tail)
            acc, cvals, cmask = self.step_fn(acc, vals, ok)
            cv = np.asarray(cvals).reshape(-1)
            cm = np.broadcast_to(np.asarray(cmask).astype(bool),
                                 np.asarray(cvals).shape).reshape(-1)
            self._host_syncs += 1
            children = cv[cm]                      # row-major ⇒ deterministic
            for i in range(0, len(children), self.batch):
                st = self._enq_chunk(st, children[i:i + self.batch])
            rounds += 1
            processed += k
            spawned += len(children)
            max_occ = max(max_occ, st.occupancy)
        self.stats = {"rounds": rounds, "processed": processed,
                      "spawned": spawned, "max_occupancy": max_occ,
                      "drained": int(st.occupancy == 0),
                      "host_syncs": self._host_syncs, "fused": 0}
        if st.occupancy > 0:
            raise RuntimeError(
                f"round loop truncated at max_rounds={max_rounds} with "
                f"occupancy {st.occupancy}: not quiescent "
                f"(stats['drained']=0)")
        return acc, st


# ---------------------------------------------------------------------------
# Priority rounds on the device heap (DESIGN.md § 5.6)
# ---------------------------------------------------------------------------


class PriorityRoundRunner:
    """``RoundRunner``'s priority twin: drives ``step_fn`` to quiescence
    through the batched device heap (``heap_planes``).  One round pops
    the ``batch`` smallest (key, val) pairs (EDF: earliest deadlines),
    runs the jitted step, and inserts the children it emits in row-major
    order — every heap batch is applied in batch-index order, so the whole
    run is bit-deterministic exactly like the FIFO rounds.  ``fused=True`` (default) chains the
    pop/insert batches under one device-resident ``lax.while_loop``."""

    def __init__(self, step_fn: PriorityStepFn, *, capacity_log2: int = 10,
                 batch: int = 64, arity_log2: int = 2, fused: bool = True,
                 sync_every: int = 0, telemetry=None, spans=None,
                 compact=None) -> None:
        self.step_fn = jax.jit(step_fn)
        self.capacity_log2 = capacity_log2
        self.capacity = 1 << capacity_log2
        self.batch = batch
        self.arity_log2 = arity_log2
        self.fused = fused
        self.telemetry = telemetry
        self.spans = spans
        self.stats: Dict[str, int] = {}
        self.sync_log: List[Dict[str, int]] = []
        if telemetry is not None and not fused:
            raise ValueError("trace planes are in-loop state: telemetry "
                             "needs the fused engine (fused=True)")
        if spans is not None and not fused:
            raise ValueError("span planes are in-loop state: spans needs "
                             "the fused engine (fused=True)")
        if fused:
            self._engine = HeapEngine(
                step_fn, capacity_log2=capacity_log2, batch=batch,
                arity_log2=arity_log2, sync_every=sync_every,
                telemetry=telemetry, spans=spans, compact=compact)
        else:
            self._engine = None
            # legacy-path op buffers, reused across rounds (safe because
            # jnp.asarray copies and every kernel call syncs on its ok)
            self._ins_ops = np.empty(batch, np.int32)
            self._ins_k = np.empty(batch, np.int32)
            self._ins_v = np.empty(batch, np.int32)
            self._pop_ops = np.empty(batch, np.int32)
            self._pad = jnp.full((batch,), HEAP_KEY_INF, jnp.int32)

    def _apply(self, st: HeapState, ops, keys, vals):
        k, v, size, outk, outv, ok = heap_planes(
            st.keys, st.vals, jnp.asarray(st.size, jnp.int32),
            ops, keys, vals,
            cap_log2=self.capacity_log2, arity_log2=self.arity_log2)
        self._host_syncs += 1
        return HeapState(k, v, int(size)), outk, outv, ok

    def _ins_chunk(self, st: HeapState, ckeys: np.ndarray,
                   cvals: np.ndarray) -> HeapState:
        n = len(ckeys)
        assert n <= self.batch
        if st.size + n > self.capacity:
            raise RuntimeError(
                f"heap overflow: size {st.size} + {n} children exceeds "
                f"capacity {self.capacity} (raise capacity_log2 or lower "
                f"the fanout)")
        self._ins_ops.fill(-1)
        self._ins_ops[:n] = 0
        self._ins_k.fill(HEAP_KEY_INF)
        self._ins_k[:n] = ckeys
        self._ins_v.fill(-1)
        self._ins_v[:n] = cvals
        st, _, _, ok = self._apply(st, jnp.asarray(self._ins_ops),
                                   jnp.asarray(self._ins_k),
                                   jnp.asarray(self._ins_v))
        assert bool(ok[:n].all()), "capacity was checked: inserts cannot miss"
        return st

    def run(self, initial_keys: np.ndarray, initial_vals: np.ndarray,
            acc: Any = None, max_rounds: int = 10_000
            ) -> Tuple[Any, HeapState]:
        if self._engine is not None:
            try:
                return self._engine.run(initial_keys, initial_vals, acc,
                                        max_rounds)
            finally:
                self.stats = dict(self._engine.stats, fused=1)
                self.sync_log = self._engine.sync_log
        self.stats = {}
        self.sync_log = []
        self._host_syncs = 0
        st = heap_init(self.capacity_log2)
        ik = np.asarray(initial_keys, np.int32)
        iv = np.asarray(initial_vals, np.int32)
        assert ik.shape == iv.shape
        for i in range(0, len(ik), self.batch):
            st = self._ins_chunk(st, ik[i:i + self.batch],
                                 iv[i:i + self.batch])
        rounds = processed = spawned = 0
        max_occ = st.size
        while st.size > 0 and rounds < max_rounds:
            k = min(self.batch, st.size)
            self._pop_ops.fill(-1)
            self._pop_ops[:k] = 1
            st, outk, outv, ok = self._apply(st, jnp.asarray(self._pop_ops),
                                             self._pad, self._pad)
            assert bool(ok[:k].all()), "size was checked: pops cannot miss"
            acc, ckeys, cvals, cmask = self.step_fn(acc, outk, outv, ok)
            ck = np.asarray(ckeys).reshape(-1)
            cv = np.asarray(cvals).reshape(-1)
            cm = np.broadcast_to(np.asarray(cmask).astype(bool),
                                 np.asarray(ckeys).shape).reshape(-1)
            self._host_syncs += 1
            children_k, children_v = ck[cm], cv[cm]   # row-major order
            for i in range(0, len(children_k), self.batch):
                st = self._ins_chunk(st, children_k[i:i + self.batch],
                                     children_v[i:i + self.batch])
            rounds += 1
            processed += k
            spawned += len(children_k)
            max_occ = max(max_occ, st.size)
        self.stats = {"rounds": rounds, "processed": processed,
                      "spawned": spawned, "max_occupancy": max_occ,
                      "drained": int(st.size == 0),
                      "host_syncs": self._host_syncs, "fused": 0}
        if st.size > 0:
            raise RuntimeError(
                f"priority round loop truncated at max_rounds={max_rounds} "
                f"with size {st.size}: not quiescent (stats['drained']=0)")
        return acc, st


def mesh_task_round(state, spawn_vals: jax.Array, spawn_mask: jax.Array,
                    claim_mask: jax.Array, axis: str):
    """One mesh-scope task round inside shard_map: publish this chip's
    spawned tasks, then claim up to ``claim_mask.sum()`` tasks for local
    execution.  Returns (state, granted, claimed_vals, claimed_ok).

    Composes ``dist_enqueue_round`` + ``dist_dequeue_round`` — two prefix-sum
    collectives per round, the mesh analogue of a wave's two leader FAAs."""
    state, granted = dist_enqueue_round(state, spawn_vals, spawn_mask, axis)
    state, vals, ok = dist_dequeue_round(state, claim_mask, axis)
    return state, granted, vals, ok


# engine-matrix rows (tests/conftest.py parametrizes over these)
register_engine("rounds", RoundRunner, priority=False, mesh=False)
register_engine("prounds", PriorityRoundRunner, priority=True, mesh=False)
