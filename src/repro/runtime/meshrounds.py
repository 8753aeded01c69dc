"""Mesh round engines (DESIGN.md § 2.3, § 6): ``enginecore.EngineCore``
configurations one level up the hierarchy, running the whole
dequeue → step → ticket → enqueue cycle *device-resident under
shard_map*.

Three engines over two queue planes:

* ``MeshRingEngine`` — the FIFO megaround over the *replicated* ring
  (``core.distqueue.DistQueueState``): every shard carries the full
  O(ring) plane set, the claim wave is collective-free (the rebalancing
  schedule is a pure function of the replicated head/tail), and the
  publish wave costs exactly ONE psum (``mesh_round_gather``).  Kept as
  the bit-identity parity baseline for the sharded plane.
* ``ShardedMeshRingEngine`` — the same megaround over *per-shard* ring
  planes (``DistShardedQueueState``): each shard owns one
  2·(capacity/shards)-slot local ring while the (S,) head/tail ticket
  vectors stay replicated, so the loop-carry memory drops from O(ring)
  to O(ring/shards) per shard (the ``benchmarks/bench_mesh.py`` column).
  The claim schedule drains the fullest rings first
  (``dist_sharded_claim_round``); children spray round-robin by global
  publish rank with ONE ``mesh_round_gather`` meta-word psum per round
  (``dist_sharded_publish_round``), mirroring the relaxed priority
  plane's ``dist_priority_publish_round`` discipline.
* ``MeshHeapEngine`` — the priority megaround (claim → pop-min → step →
  push) over the ``core.distqueue`` priority plane, in two orderings:
  ``relaxed=True`` (per-shard local heaps, hint-ordered even-split
  claim schedule, k-relaxed delete-min — envelope in
  ``sched.relaxed.mesh_relaxation_bound``) and ``relaxed=False`` (one
  replicated heap popped in exact global min-key order).

All three are thin configurations of the fused-engine core
(DESIGN.md § 4.8): the round bodies follow the standardized ``_round``
contract, ``EngineCore.fused_loop`` builds the one jitted
``lax.while_loop``, ``_run_chunks``/``_drive`` own the host sync +
overflow/truncation contract, and each engine's loop carry is declared
once in its ``PlaneRegistry`` — the registry derives both the shard_map
specs and the measured per-shard carry bytes.  The mesh layer adds only
the shard_map boundary: ``_megaround_impl`` overrides unstack the
``P(axis)``-sharded leaves (stacked ``(1, ...)`` per shard) around the
core loop and restack them on the way out.

``MeshRoundRunner`` / ``PriorityMeshRoundRunner`` are the runner faces:
``fused=True`` (default) delegates to the engines above; ``fused=False``
keeps the legacy host-driven loop — one jitted shard_map dispatch and
one occupancy readback per round (``EngineCore._legacy_loop``) — for
step-debug, as the parity baseline, and (priority only) as the history
recorder for ``sched.plinearizability``.  Fused and legacy are
bit-identical on the replicated planes; the sharded ring is exact
against the replicated baseline on totals and order-insensitive
accumulators (claim *order* legitimately differs — the schedule is
load-aware, not rank-sliced).

Note on the varying-manual-axes checker: the per-round distqueue API
is checked with ``check_vma=True``; the engine shard_maps are built with
``check_vma=False``, and the replicated typing of their loop carry is
asserted by tests (per-shard state bit-identity) instead.

Overflow and truncation follow the core contract: a flag in the carry
exits the loop and the host driver raises ``RuntimeError`` at the next
sync.  Accumulators are *per-shard* (each shard steps only its claimed
batch), returned stacked with a leading shard axis unless ``combine``
reduces them (BFS: elementwise min over shards).

``FusedMeshRounds`` / ``FusedPriorityMeshRounds`` are deprecated shims
over ``MeshRingEngine`` / ``MeshHeapEngine``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.distqueue import (DistHeapState, DistQueueState,
                              DistShardedQueueState, claim_schedule,
                              dist_claim_round, dist_heap_init,
                              dist_priority_publish_compact_round,
                              dist_priority_publish_round,
                              dist_publish_compact_round, dist_publish_round,
                              dist_queue_init, dist_sharded_claim_round,
                              dist_sharded_publish_round,
                              dist_sharded_queue_init,
                              priority_claim_schedule)
from ..kernels.compact import compact_width
from ..kernels.heap_batch import (KEY_INF as HEAP_KEY_INF, heap_insert_masked,
                                  heap_pop_count)
from ..kernels.ring_slots import enq_planes
from ..obs.spans import Spans, span_record, span_tick
from ..obs.trace import Telemetry, masked_min_max
from .enginecore import (EngineCore, _sds, deprecated_engine,
                         register_engine)
from .fusedrounds import IDX_BOT, PriorityStepFn, StepFn

__all__ = ["FusedMeshRounds", "FusedPriorityMeshRounds", "MeshHeapEngine",
           "MeshRingEngine", "MeshRoundRunner", "PriorityMeshRoundRunner",
           "ShardedMeshRingEngine"]


def _unstack(x):
    return jax.tree_util.tree_map(lambda a: a[0], x)


def _restack(x):
    return jax.tree_util.tree_map(lambda a: a[None], x)


class _MeshFifoBase(EngineCore):
    """Shared FIFO-mesh scaffolding: constructor fields, capacity
    validation, and the host-side acc broadcast."""

    def __init__(self, step_fn: StepFn, *, mesh, axis: str = "data",
                 capacity_log2: int = 10, batch: int = 64,
                 sync_every: int = 0,
                 combine: Callable[[Any], Any] = None,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None) -> None:
        self.step_fn = step_fn
        self.mesh = mesh
        self.axis = axis
        self.shards = int(mesh.shape[axis])
        self.capacity_log2 = capacity_log2
        self.capacity = 1 << capacity_log2
        self.nslots_log2 = capacity_log2 + 1
        self.batch = batch
        if batch * self.shards > self.capacity:
            raise ValueError(
                f"mesh batch {batch} x {self.shards} shards exceeds ring "
                f"capacity {self.capacity}")
        self.sync_every = sync_every
        self.combine = combine
        self.telemetry = telemetry
        self.spans = spans
        self.compact = compact
        self._reset()

    def _initial_carry(self, state, acc):
        acc = jax.tree_util.tree_map(jnp.asarray, acc)
        acc = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (self.shards,) + x.shape),
            acc)
        return state, acc

    def _finish(self, state):
        acc = state[1]
        if self.combine is not None:
            acc = self.combine(acc)
        return acc, state[0]


class MeshRingEngine(_MeshFifoBase):
    """The replicated-ring FIFO megaround: one jitted shard_map call runs
    up to ``limit`` rounds on device; host sync only at quiescence (or
    every ``sync_every`` rounds).  ``run`` mirrors ``RingEngine.run`` and
    returns (acc, final ``DistQueueState``) where acc carries a leading
    shard axis unless ``combine`` reduces it."""

    def __init__(self, step_fn: StepFn, *, mesh, axis: str = "data",
                 capacity_log2: int = 10, batch: int = 64,
                 sync_every: int = 0,
                 combine: Callable[[Any], Any] = None,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None) -> None:
        super().__init__(step_fn, mesh=mesh, axis=axis,
                         capacity_log2=capacity_log2, batch=batch,
                         sync_every=sync_every, combine=combine,
                         telemetry=telemetry, spans=spans, compact=compact)
        n2 = 2 << capacity_log2
        reg = self.registry
        reg.register("ring", (_sds((n2,)),) * 4 + (_sds(()), _sds(())))
        self._register_obs_planes(self.shards, stacked=True,
                                  births_shape=(n2,))
        # in shard_map, P() = replicated, P(axis) = sharded; a bare spec
        # serves as a pytree-prefix for a whole subtree (the qstate
        # NamedTuple, the acc tree).  acc rides stacked (shards, ...) so
        # successive chunk calls (sync_every heartbeats) compose.  The
        # trailing (tp, sp, births) slots always exist in the specs: None
        # is a valid pytree leaf-set for any spec, and the all-None call
        # compiles to the exact unobserved graph.  The TracePlane is
        # replicated (every record field derives from replicated values);
        # the SpanPlane is sharded (each shard records its own claims);
        # the births plane mirrors the ring field planes — replicated.
        obs = (reg.spec("trace"), reg.spec("span"), reg.spec("births"))
        in_specs = (reg.spec("ring"), P(self.axis),
                    P(), P(), P(), P()) + obs
        out_specs = (reg.spec("ring"), P(self.axis),
                     P(), P(), P(), P(), P()) + obs
        self._carry_specs = in_specs
        self._megaround = jax.jit(jax.shard_map(
            self._megaround_impl, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs,
            check_vma=False))   # see the module note

    # -- seeding (host-side, before shard_map: planes are plain jnp) --------
    def _seed(self, state: DistQueueState,
              initial: np.ndarray) -> DistQueueState:
        k = len(initial)
        if k > self.capacity:
            raise RuntimeError(
                f"mesh ring overflow: {k} seed values exceed capacity "
                f"{self.capacity} (raise capacity_log2)")
        if k == 0:
            return state
        base = int(np.int64(np.asarray(state.tail)))
        t = (base + np.arange(k, dtype=np.int64)) % (2 ** 32)
        tickets = jnp.asarray(np.where(t >= 2 ** 31, t - 2 ** 32, t)
                              .astype(np.int32))
        cyc, saf, enq, idx, ok = enq_planes(
            state.cycles, state.safes, state.enqs, state.idxs, tickets,
            jnp.asarray(initial), state.head,
            nslots_log2=self.nslots_log2, idx_bot=IDX_BOT)
        assert bool(np.asarray(ok).all()), "exact tickets cannot miss"
        return DistQueueState(cyc, saf, enq, idx,
                              tail=state.tail + jnp.int32(k),
                              head=state.head)

    @staticmethod
    def _occ_of(q: DistQueueState):
        return q.tail - q.head

    # -- one mesh round (the standardized ``_round`` contract) --------------
    def _round(self, state: DistQueueState, acc, tel: bool = False,
               sp=None, births=None):
        """claim (no collective) → step → publish (one psum).  Telemetry
        record fields all derive from already-replicated values — zero
        extra collectives.  With ``sp`` the claim reads birth stamps, the
        publish stamps ``sp.round`` into the replicated births plane, and
        each shard records its own local claims into its sharded
        SpanPlane row (DESIGN.md § 7.6)."""
        sps = sp is not None
        occ = state.tail - state.head
        k = jnp.minimum(occ, jnp.int32(self.shards * self.batch))
        with jax.named_scope("repro.ring.deq"):
            cr = dist_claim_round(state, k, self.batch, self.axis,
                                  with_grid=tel, births=births)
        state, vals, ok = cr[0], cr[1], cr[2]
        i = 3
        if tel:
            gvals, gok = cr[i]
            i += 1
        if sps:
            bout = cr[i]
        with jax.named_scope("repro.step"):
            acc, cvals, cmask = self.step_fn(acc, vals, ok)
        cm = jnp.broadcast_to(cmask.astype(bool), cvals.shape).reshape(-1)
        cv = cvals.reshape(-1).astype(jnp.int32)
        # dense-wave rule (DESIGN.md § 4.4): each shard compacts its child
        # block to the capacity bound before the exchange — same single
        # psum, O(width) instead of O(B·F) payload, bit-identical planes.
        # The decision is static (trace-time): exactly one path compiles.
        wdth = compact_width(cv.shape[0], self.capacity, self.compact)
        with jax.named_scope("repro.publish"):
            if wdth is None:
                pr = dist_publish_round(
                    state, cv, cm.astype(jnp.int32), self.axis,
                    capacity=self.capacity, with_counts=tel, births=births,
                    birth_round=sp.round if sps else None)
            else:
                pr = dist_publish_compact_round(
                    state, cv, cm.astype(jnp.int32), self.axis,
                    capacity=self.capacity, width=wdth, with_counts=tel,
                    births=births, birth_round=sp.round if sps else None)
        state, _, total, over = pr[0], pr[1], pr[2], pr[3]
        j = 4
        telinfo = None
        if tel:
            pushes = pr[j]
            j += 1
            cs_active, _ = claim_schedule(k, self.shards, self.batch)
            pops = cs_active.reshape(self.shards, self.batch).sum(
                1, dtype=jnp.int32)
            mn, mx = masked_min_max(gvals, gok)   # FIFO: payload extrema
            occs = jnp.broadcast_to(state.tail - state.head,
                                    (self.shards,))   # replicated ring
            telinfo = (pops, pushes, occs, mn, mx)
        if sps:
            births = pr[j]
            me = jax.lax.axis_index(self.axis)
            cls = self._span_cls(vals, jnp.full_like(vals, me))
            sp = span_record(sp, cls, sp.round - bout, ok, vals)
            sp = span_tick(sp)
        return state, acc, k, total, over, telinfo, sp, births

    # -- shard_map boundary: unstack/restack the P(axis) leaves -------------
    def _megaround_impl(self, qstate, acc, processed, spawned, max_occ,
                        limit, tp=None, sp=None, births=None):
        acc = _unstack(acc)
        sps = sp is not None
        if sps:   # sharded SpanPlane arrives stacked (1, ...) per shard
            sp = _unstack(sp)
        out = super()._megaround_impl(qstate, acc, processed, spawned,
                                      max_occ, limit, tp, sp, births)
        sp_out = _restack(out[8]) if sps else out[8]
        return (out[0], _restack(out[1])) + out[2:8] + (sp_out, out[9])

    def run(self, initial: np.ndarray, acc: Any = None,
            max_rounds: int = 10_000) -> Tuple[Any, DistQueueState]:
        """Seed the replicated ring and run mesh megarounds to global
        quiescence.  Sync contract: one host block per ``sync_every``
        chunk (once total when 0) on the replicated occupancy; all other
        coordination stays on device (one psum per round).  Determinism:
        bit-identical to the legacy per-round path — same acc leaves,
        planes, head/tail, stats.  Raises ``RuntimeError`` on ring
        overflow or truncation at the next sync."""
        self._reset()
        with jax.profiler.TraceAnnotation("repro.seed"):
            st = self._seed(dist_queue_init(self.capacity),
                            np.asarray(initial, np.int32).reshape(-1))
            st, acc = self._initial_carry(st, acc)
            occ0 = jnp.int32(np.asarray(st.tail - st.head))
            state = [st, acc, jnp.int32(0), jnp.int32(0), occ0]
            ext = [self._tel_init(self.shards),
                   self._span_init(self.shards, stacked=True),
                   self._births_init((2 << self.capacity_log2,))]
        self._run_chunks(
            state, ext,
            lambda q: int(np.int32(np.asarray(q.tail - q.head))),
            "mesh ring", max_rounds)
        return self._finish(state)


class ShardedMeshRingEngine(_MeshFifoBase):
    """The per-shard-ring FIFO megaround (DESIGN.md § 2.3): each shard
    loop-carries ONE 2·(capacity/shards)-slot local ring plus the (S,)
    replicated ticket vectors — O(ring/shards) carry bytes per shard
    (``loop_carry_bytes``, measured in bench_mesh) versus the replicated
    engine's O(ring).  The claim schedule is load-aware
    (fullest-rings-first, collective-free); the publish sprays children
    round-robin by global rank in ONE meta-word psum.  Exact against the
    replicated baseline on totals and order-insensitive accumulators;
    claim *order* differs by design, so plane bit-identity is not a
    contract here.  Spans are unsupported: the local rings keep no
    replicated birth-stamp rider."""

    def __init__(self, step_fn: StepFn, *, mesh, axis: str = "data",
                 capacity_log2: int = 10, batch: int = 64,
                 sync_every: int = 0,
                 combine: Callable[[Any], Any] = None,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None) -> None:
        if spans is not None:
            raise ValueError(
                "sharded ring planes keep no replicated birth-stamp "
                "rider: spans needs the replicated mesh engine "
                "(sharded=False)")
        super().__init__(step_fn, mesh=mesh, axis=axis,
                         capacity_log2=capacity_log2, batch=batch,
                         sync_every=sync_every, combine=combine,
                         telemetry=telemetry, spans=spans, compact=compact)
        self.local_capacity = self.capacity // self.shards
        self.lslots_log2 = (capacity_log2
                            - (self.shards.bit_length() - 1)) + 1
        n2 = 2 * self.local_capacity
        reg = self.registry
        # global (stacked) shapes; the registry divides sharded groups by
        # the shard count in bytes_per_shard — the O(ring/shards) claim
        reg.register("ring", (_sds((self.shards, n2)),) * 4, sharded=True)
        reg.register("tickets", (_sds((self.shards,)), _sds((self.shards,))))
        self._register_obs_planes(self.shards, stacked=True)
        qspec = DistShardedQueueState(
            *((reg.spec("ring"),) * 4),
            tails=reg.spec("tickets"), heads=reg.spec("tickets"))
        obs = (reg.spec("trace"), reg.spec("span"), reg.spec("births"))
        in_specs = (qspec, P(self.axis), P(), P(), P(), P()) + obs
        out_specs = (qspec, P(self.axis), P(), P(), P(), P(), P()) + obs
        self._carry_specs = in_specs
        self._megaround = jax.jit(jax.shard_map(
            self._megaround_impl, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs,
            check_vma=False))   # see the module note

    # -- seeding: round-robin spray by seed rank into the local rings -------
    def _seed(self, state: DistShardedQueueState,
              initial: np.ndarray) -> DistShardedQueueState:
        k = len(initial)
        if k > self.capacity:
            raise RuntimeError(
                f"sharded mesh ring overflow: {k} seed values exceed "
                f"capacity {self.capacity} (raise capacity_log2)")
        if k == 0:
            return state
        planes = [list(np.asarray(p)) for p in
                  (state.cycles, state.safes, state.enqs, state.idxs)]
        tails = np.asarray(state.tails).copy()
        shard_of = np.arange(k) % self.shards
        for s in range(self.shards):
            vals = initial[shard_of == s]
            c = len(vals)
            if c == 0:
                continue
            t = (np.int64(np.uint32(tails[s]))
                 + np.arange(c, dtype=np.int64)) % (2 ** 32)
            tickets = jnp.asarray(np.where(t >= 2 ** 31, t - 2 ** 32, t)
                                  .astype(np.int32))
            cyc, saf, enq, idx, ok = enq_planes(
                jnp.asarray(planes[0][s]), jnp.asarray(planes[1][s]),
                jnp.asarray(planes[2][s]), jnp.asarray(planes[3][s]),
                tickets, jnp.asarray(vals), state.heads[s],
                nslots_log2=self.lslots_log2, idx_bot=IDX_BOT)
            assert bool(np.asarray(ok).all()), "exact tickets cannot miss"
            for p, new in zip(planes, (cyc, saf, enq, idx)):
                p[s] = np.asarray(new)
            tails[s] = np.int32(np.int64(tails[s]) + c)
        return DistShardedQueueState(
            *(jnp.asarray(np.stack(p)) for p in planes),
            tails=jnp.asarray(tails), heads=state.heads)

    @staticmethod
    def _occ_of(q: DistShardedQueueState):
        return jnp.sum(q.tails - q.heads)

    # -- one sharded round (the standardized ``_round`` contract) -----------
    def _round(self, state: DistShardedQueueState, acc, tel: bool = False,
               sp=None, births=None):
        """claim (no collective: load-aware schedule over the replicated
        (S,) occupancies) → step → publish (ONE psum: child blocks +
        count/extrema meta words).  The local claim extrema are NOT
        replicated, so with telemetry on they ride the publish psum as
        ``pop_meta`` words — one-collective-per-round still holds."""
        planes = (state.cycles, state.safes, state.enqs, state.idxs)
        with jax.named_scope("repro.ring.deq"):
            planes, heads, vals, ok, counts = dist_sharded_claim_round(
                planes, state.heads, state.tails, self.batch, self.axis,
                nslots_log2=self.lslots_log2)
        with jax.named_scope("repro.step"):
            acc, cvals, cmask = self.step_fn(acc, vals, ok)
        cm = jnp.broadcast_to(cmask.astype(bool), cvals.shape).reshape(-1)
        cv = cvals.reshape(-1).astype(jnp.int32)
        pop_meta = masked_min_max(vals, ok) if tel else None
        # dense-wave bound: a round spawning more than the GLOBAL capacity
        # must overflow some local ring, where both paths install nothing
        wdth = compact_width(cv.shape[0], self.capacity, self.compact)
        with jax.named_scope("repro.publish"):
            res = dist_sharded_publish_round(
                planes, heads, state.tails, cv, cm.astype(jnp.int32),
                self.axis, nslots_log2=self.lslots_log2,
                local_capacity=self.local_capacity, width=wdth,
                pop_meta=pop_meta)
        planes, tails, total, over = res[0], res[1], res[2], res[3]
        state = DistShardedQueueState(*planes, tails=tails, heads=heads)
        telinfo = None
        if tel:
            assigned, mins, maxs = res[4], res[5], res[6]
            telinfo = (counts, assigned, tails - heads,
                       jnp.min(mins), jnp.max(maxs))
        return state, acc, jnp.sum(counts), total, over, telinfo, sp, births

    # -- shard_map boundary: unstack/restack the P(axis) plane leaves -------
    def _megaround_impl(self, qstate, acc, processed, spawned, max_occ,
                        limit, tp=None, sp=None, births=None):
        qstate = qstate._replace(
            cycles=qstate.cycles[0], safes=qstate.safes[0],
            enqs=qstate.enqs[0], idxs=qstate.idxs[0])
        acc = _unstack(acc)
        out = EngineCore._megaround_impl(
            self, qstate, acc, processed, spawned, max_occ, limit,
            tp, sp, births)
        q = out[0]
        q = q._replace(cycles=q.cycles[None], safes=q.safes[None],
                       enqs=q.enqs[None], idxs=q.idxs[None])
        return (q, _restack(out[1])) + out[2:]

    def run(self, initial: np.ndarray, acc: Any = None,
            max_rounds: int = 10_000) -> Tuple[Any, DistShardedQueueState]:
        """Seed the per-shard rings (round-robin by seed rank) and run to
        global quiescence; same sync/overflow/truncation contract as the
        replicated engine.  Returns (acc, final ``DistShardedQueueState``
        with globally-stacked planes)."""
        self._reset()
        with jax.profiler.TraceAnnotation("repro.seed"):
            st = self._seed(
                dist_sharded_queue_init(self.capacity, self.shards),
                np.asarray(initial, np.int32).reshape(-1))
            st, acc = self._initial_carry(st, acc)
            occ0 = jnp.int32(int(np.asarray(st.tails - st.heads).sum()))
            state = [st, acc, jnp.int32(0), jnp.int32(0), occ0]
            ext = [self._tel_init(self.shards), None, None]
        self._run_chunks(
            state, ext,
            lambda q: int(np.asarray(q.tails - q.heads).sum()),
            "sharded mesh ring", max_rounds)
        return self._finish(state)


class MeshRoundRunner(_MeshFifoBase):
    """Mesh twin of ``RoundRunner``: ``fused=True`` (default) delegates
    to ``MeshRingEngine`` (or ``ShardedMeshRingEngine`` with
    ``sharded=True``); ``fused=False`` keeps the legacy host-driven loop
    — one jitted shard_map dispatch and one occupancy readback per round
    (the ``mesh_task_round`` pathology the fused engines removed), kept
    for step-debug and as the parity baseline.  Fused and legacy are
    bit-identical on the replicated ring."""

    def __init__(self, step_fn: StepFn, *, mesh, axis: str = "data",
                 capacity_log2: int = 10, batch: int = 64,
                 fused: bool = True, sharded: bool = False,
                 sync_every: int = 0,
                 combine: Callable[[Any], Any] = None,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None) -> None:
        super().__init__(step_fn, mesh=mesh, axis=axis,
                         capacity_log2=capacity_log2, batch=batch,
                         sync_every=sync_every, combine=combine,
                         telemetry=telemetry, spans=spans, compact=compact)
        self.fused = fused
        self.sharded = sharded
        if spans is not None and not fused:
            raise ValueError(
                "span planes are in-loop state: spans needs the fused "
                "engine (fused=True)")
        if sharded and not fused:
            raise ValueError(
                "sharded rings are a fused-engine configuration (the "
                "per-shard planes live in the megaround carry): use "
                "fused=True")
        if fused:
            cls = ShardedMeshRingEngine if sharded else MeshRingEngine
            self._engine = cls(
                step_fn, mesh=mesh, axis=axis, capacity_log2=capacity_log2,
                batch=batch, sync_every=sync_every, combine=combine,
                telemetry=telemetry, spans=spans, compact=compact)
        else:
            self._engine = None
            # legacy: acc rides stacked (shards, ...) through P(axis)
            self._round_jit = jax.jit(jax.shard_map(
                self._legacy_round, mesh=self.mesh,
                in_specs=(P(), P(self.axis)),
                out_specs=(P(), P(self.axis), P(), P(), P()),
                check_vma=False))   # acc diverges per shard (P(axis) io)

    # reuse the replicated engine's round/seed for the legacy baseline
    _seed = MeshRingEngine._seed
    _round = MeshRingEngine._round
    _occ_of = MeshRingEngine._occ_of

    def _legacy_round(self, qstate, acc):
        acc = _unstack(acc)
        qstate, acc, k, total, over = self._round(qstate, acc)[:5]
        return qstate, _restack(acc), k, total, over

    def loop_carry_bytes(self, shards: int = None) -> int:
        # the fused engine owns the plane registry; the legacy loop
        # carries nothing between dispatches (host-resident state)
        if self._engine is not None:
            return self._engine.loop_carry_bytes(shards)
        return super().loop_carry_bytes(shards)

    def run(self, initial: np.ndarray, acc: Any = None,
            max_rounds: int = 10_000) -> Tuple[Any, DistQueueState]:
        """Run to quiescence on the selected engine.  ``fused=True``:
        the megaround contract (host sync only at quiescence /
        ``sync_every``); ``fused=False``: one shard_map dispatch and one
        occupancy readback per round (``host_syncs == rounds``).  Both
        bit-deterministic; both raise on overflow/truncation."""
        if self._engine is not None:
            try:
                return self._engine.run(initial, acc, max_rounds)
            finally:
                self.stats = dict(self._engine.stats, fused=1)
                self.sync_log = self._engine.sync_log
        self._reset()
        st = self._seed(dist_queue_init(self.capacity),
                        np.asarray(initial, np.int32).reshape(-1))
        st, acc = self._initial_carry(st, acc)
        occ0 = int(np.int32(np.asarray(st.tail - st.head)))

        def round_call(q, acc):
            q, acc, k, total, over = self._round_jit(q, acc)
            return q, acc, k, total, over, None

        st, acc = self._legacy_loop(
            st, acc, round_call, occ0,
            lambda q: int(np.int32(np.asarray(q.tail - q.head))),
            "mesh ring", max_rounds)
        if self.combine is not None:
            acc = self.combine(acc)
        return acc, st


# ---------------------------------------------------------------------------
# priority mesh rounds (DESIGN.md § 6)
# ---------------------------------------------------------------------------


class _PriorityMeshBase(EngineCore):
    """Shared priority-mesh machinery: seeding and the one-round bodies.
    ``relaxed=True`` = per-shard local heaps with hint-ordered claim
    rebalancing; ``relaxed=False`` = one replicated heap popped in exact
    global min-key order."""

    def __init__(self, step_fn: PriorityStepFn, *, mesh, axis: str = "data",
                 capacity_log2: int = 10, batch: int = 64,
                 arity_log2: int = 2, relaxed: bool = True,
                 sync_every: int = 0,
                 combine: Callable[[Any], Any] = None,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None,
                 split: bool = False) -> None:
        self.step_fn = step_fn
        self.telemetry = telemetry
        self.spans = spans
        if split and spans is not None:
            raise ValueError(
                "split payloads ride the heap's rider plane, which spans "
                "already uses for birth stamps: spans and split are "
                "mutually exclusive")
        self.mesh = mesh
        self.axis = axis
        self.shards = int(mesh.shape[axis])
        self.capacity_log2 = capacity_log2
        self.capacity = 1 << capacity_log2
        self.batch = batch
        self.arity_log2 = arity_log2
        self.relaxed = relaxed
        self.compact = compact
        self.split = split
        self.combine = combine
        if relaxed and batch > self.capacity:
            raise ValueError(
                f"batch {batch} exceeds per-shard heap capacity "
                f"{self.capacity}")
        if not relaxed and batch * self.shards > self.capacity:
            raise ValueError(
                f"mesh batch {batch} x {self.shards} shards exceeds heap "
                f"capacity {self.capacity}")
        self.sync_every = sync_every
        self._reset()

    # -- seeding (host-side, before shard_map) ------------------------------
    def _seed(self, ik: np.ndarray, iv: np.ndarray, ia=None):
        """Install the seed (key, val) pairs.  Relaxed mode sprays them
        round-robin by seed rank (``rank % shards``) into the per-shard
        heaps and returns stacked ``(keys (S,cap), vals (S,cap),
        sizes (S,), hints (S,))``; strict mode installs everything into
        the one replicated heap and returns ``(keys, vals, size)``.  In
        split mode ``ia`` carries per-seed aux words installed through
        the rider plane; it trails the return tuple."""
        k = len(ik)
        spl = ia is not None
        if not self.relaxed:
            if k > self.capacity:
                raise RuntimeError(
                    f"mesh heap overflow: {k} seed values exceed capacity "
                    f"{self.capacity} (raise capacity_log2)")
            st = dist_heap_init(self.capacity)
            aux = jnp.zeros((self.capacity,), jnp.int32) if spl else None
            if k == 0:
                return ((st.keys, st.vals, st.size)
                        + ((aux,) if spl else ()))
            out = heap_insert_masked(
                st.keys, st.vals, st.size, jnp.asarray(ik), jnp.asarray(iv),
                jnp.ones((k,), bool), cap_log2=self.capacity_log2,
                arity_log2=self.arity_log2, rider=aux,
                oprider=jnp.asarray(ia) if spl else None)
            keys, vals, size, ok = out[0], out[1], out[2], out[5]
            assert bool(np.asarray(ok).all()), "capacity checked: cannot miss"
            return (keys, vals, size) + ((out[6],) if spl else ())
        shard_of = np.arange(k) % self.shards
        per = [np.flatnonzero(shard_of == s) for s in range(self.shards)]
        worst = max((len(p) for p in per), default=0)
        if worst > self.capacity:
            raise RuntimeError(
                f"mesh heap overflow: {worst} seed values land on one shard, "
                f"exceeding per-shard capacity {self.capacity} (raise "
                f"capacity_log2)")
        keys_l, vals_l, sizes, hints, aux_l = [], [], [], [], []
        for idx in per:
            st = dist_heap_init(self.capacity)
            kk, vv, sz = st.keys, st.vals, st.size
            aa = jnp.zeros((self.capacity,), jnp.int32) if spl else None
            if len(idx):
                out = heap_insert_masked(
                    kk, vv, sz, jnp.asarray(ik[idx]), jnp.asarray(iv[idx]),
                    jnp.ones((len(idx),), bool),
                    cap_log2=self.capacity_log2, arity_log2=self.arity_log2,
                    rider=aa, oprider=jnp.asarray(ia[idx]) if spl else None)
                kk, vv, sz, ok = out[0], out[1], out[2], out[5]
                if spl:
                    aa = out[6]
                assert bool(np.asarray(ok).all())
            keys_l.append(kk)
            vals_l.append(vv)
            sizes.append(int(sz))
            hints.append(int(jnp.min(kk)))
            aux_l.append(aa)
        res = (jnp.stack(keys_l), jnp.stack(vals_l),
               jnp.asarray(sizes, jnp.int32), jnp.asarray(hints, jnp.int32))
        return res + ((jnp.stack(aux_l),) if spl else ())

    def _occ_of(self, q):
        return jnp.sum(q[2]) if self.relaxed else q[2]

    def _round(self, qstate, acc, tel: bool = False, sp=None, births=None):
        body = self._round_relaxed if self.relaxed else self._round_strict
        return body(*qstate, acc, tel=tel, sp=sp, births=births)

    # -- one priority mesh round, relaxed ordering --------------------------
    def _round_relaxed(self, keys, vals, sizes, hints, acc,
                       tel: bool = False, sp=None, births=None):
        """claim (no collective: hint-ordered schedule over replicated
        sizes/hints) → masked pop wave on the local heap → step →
        publish (ONE psum) → masked insert of this shard's sprayed share.
        The popped-key extrema ride the publish psum as widened meta
        words (``pop_meta``), so the one-collective-per-round invariant
        holds with telemetry on.  With ``sp`` the per-shard births plane
        rides the local heap as a rider value plane (DESIGN.md § 7.6).
        The legacy trace tuple trails the standardized 8-tuple."""
        sps = sp is not None
        spl = self.split
        me = jax.lax.axis_index(self.axis)
        with jax.named_scope("repro.heap.pop"):
            counts = priority_claim_schedule(jnp.sum(sizes), self.shards,
                                             self.batch, hints, sizes)
            if sps or spl:
                # the rider plane carries birth stamps (spans) or the split
                # aux words — mutually exclusive by construction
                (keys, vals, size, outk, outv, ok, births,
                 bout) = heap_pop_count(
                    keys, vals, sizes[me], counts[me], batch=self.batch,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2, rider=births)
            else:
                keys, vals, size, outk, outv, ok = heap_pop_count(
                    keys, vals, sizes[me], counts[me], batch=self.batch,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2)
        with jax.named_scope("repro.step"):
            if spl:
                acc, ckeys, cvals, caux, cmask = self.step_fn(
                    acc, outk, outv, bout, ok)
                caf = caux.reshape(-1).astype(jnp.int32)
            else:
                acc, ckeys, cvals, cmask = self.step_fn(acc, outk, outv, ok)
                caf = None
        cm = jnp.broadcast_to(cmask.astype(bool), ckeys.shape).reshape(-1)
        ckf = ckeys.reshape(-1).astype(jnp.int32)
        cvf = cvals.reshape(-1).astype(jnp.int32)
        # local popped-key extrema (telemetry rides the publish psum)
        pop_meta = masked_min_max(outk, ok) if tel else None
        # dense-wave rule (DESIGN.md § 4.4): the relaxed install bound is
        # shards·capacity — any round spawning more must overflow some
        # shard's heap, where both paths install nothing
        wdth = compact_width(ckf.shape[0], self.shards * self.capacity,
                             self.compact)
        with jax.named_scope("repro.publish"):
            if wdth is None:
                res = dist_priority_publish_round(
                    ckf, cvf, cm.astype(jnp.int32), jnp.min(keys), size,
                    self.axis, pop_meta=pop_meta, aux=caf)
            else:
                res = dist_priority_publish_compact_round(
                    ckf, cvf, cm.astype(jnp.int32), jnp.min(keys), size,
                    self.axis, width=wdth, pop_meta=pop_meta, aux=caf)
        gk, gv = res[0], res[1]
        i = 2
        if spl:
            gaux = res[i]
            i += 1
        gactive, ranks, total, hints_pop, sizes_pop = res[i:i + 5]
        i += 5
        if tel:
            pop_mins, pop_maxs = res[i], res[i + 1]
        shard_of = jnp.where(gactive, ranks % self.shards, self.shards)
        if wdth is None:
            assigned = (jnp.zeros((self.shards + 1,), jnp.int32)
                        .at[shard_of].add(1))[:self.shards]
        else:
            # ranks are the round-robin prefix 0..total-1, so the
            # scatter-add has the closed form total//n + (s < total%n) —
            # computed from the TRUE total, it stays exact even when a
            # compact block clamped lanes (only possible when over)
            s_ix = jnp.arange(self.shards, dtype=jnp.int32)
            assigned = (total // self.shards
                        + (s_ix < total % self.shards).astype(jnp.int32))
        over = jnp.any(sizes_pop + assigned > self.capacity)
        mine = gactive & (shard_of == me) & ~over
        with jax.named_scope("repro.heap.insert"):
            if sps or spl:
                keys, vals, size, _, _, _, births, _ = heap_insert_masked(
                    keys, vals, size, gk, gv, mine,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2,
                    rider=births, oprider=gaux if spl else sp.round)
            else:
                keys, vals, size, _, _, _ = heap_insert_masked(
                    keys, vals, size, gk, gv, mine,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2)
        ckmin = (jnp.full((self.shards + 1,), HEAP_KEY_INF, jnp.int32)
                 .at[shard_of].min(jnp.where(gactive, gk, HEAP_KEY_INF))
                 )[:self.shards]
        hints = jnp.where(over, hints_pop, jnp.minimum(hints_pop, ckmin))
        sizes = jnp.where(over, sizes_pop, sizes_pop + assigned)
        total = jnp.where(over, 0, total)
        telinfo = None
        if tel:
            telinfo = (counts, jnp.where(over, 0, assigned), sizes,
                       jnp.min(pop_mins), jnp.max(pop_maxs))
        if sps:
            cls = self._span_cls(outk, jnp.full_like(outk, me))
            sp = span_record(sp, cls, sp.round - bout, ok, outv)
            sp = span_tick(sp)
        trace = (outk, outv, ok, gk, gv, gactive)
        return ((keys, vals, sizes, hints), acc, jnp.sum(counts), total,
                over, telinfo, sp, births, trace)

    # -- one priority mesh round, strict ordering ---------------------------
    def _round_strict(self, keys, vals, size, acc, tel: bool = False,
                      sp=None, births=None):
        """Every shard applies the identical full-width pop wave to the
        replicated heap (exact global min-key order), steps only its
        ``claim_schedule`` slice, and installs ALL gathered children —
        the planes stay replicated by construction.  The pop wave is
        replicated full-width, so telemetry extrema are free.  With
        ``sp`` every shard computes identical pops/inserts but records
        only its own slice into its sharded SpanPlane, so the host-side
        shard merge counts each task once (DESIGN.md § 7.6).  The legacy
        trace tuple trails the standardized 8-tuple."""
        sps = sp is not None
        spl = self.split
        me = jax.lax.axis_index(self.axis)
        sb = self.shards * self.batch
        k = jnp.minimum(size, jnp.int32(sb))
        with jax.named_scope("repro.heap.pop"):
            if sps or spl:
                (keys, vals, size, outk, outv, _, births,
                 outb) = heap_pop_count(
                    keys, vals, size, k, batch=sb,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2, rider=births)
            else:
                keys, vals, size, outk, outv, _ = heap_pop_count(
                    keys, vals, size, k, batch=sb,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2)
            active, ranks = claim_schedule(k, self.shards, self.batch)
            act_l = active.reshape(self.shards, self.batch)[me]
            rk_l = ranks.reshape(self.shards, self.batch)[me]
            outk_l = jnp.where(act_l, outk[rk_l], HEAP_KEY_INF)
            outv_l = jnp.where(act_l, outv[rk_l], -1)
        with jax.named_scope("repro.step"):
            if spl:
                outa_l = jnp.where(act_l, outb[rk_l], 0)
                acc, ckeys, cvals, caux, cmask = self.step_fn(
                    acc, outk_l, outv_l, outa_l, act_l)
                caf = caux.reshape(-1).astype(jnp.int32)
            else:
                acc, ckeys, cvals, cmask = self.step_fn(acc, outk_l, outv_l,
                                                        act_l)
                caf = None
        cm = jnp.broadcast_to(cmask.astype(bool), ckeys.shape).reshape(-1)
        ckf = ckeys.reshape(-1).astype(jnp.int32)
        cvf = cvals.reshape(-1).astype(jnp.int32)
        # dense-wave rule (DESIGN.md § 4.4): the strict install bound is
        # the replicated heap's capacity
        wdth = compact_width(ckf.shape[0], self.capacity, self.compact)
        with jax.named_scope("repro.publish"):
            if wdth is None:
                res = dist_priority_publish_round(
                    ckf, cvf, cm.astype(jnp.int32), jnp.min(keys), size,
                    self.axis, aux=caf)
            else:
                res = dist_priority_publish_compact_round(
                    ckf, cvf, cm.astype(jnp.int32), jnp.min(keys), size,
                    self.axis, width=wdth, aux=caf)
        gk, gv = res[0], res[1]
        i = 2
        if spl:
            gaux = res[i]
            i += 1
        gactive, total = res[i], res[i + 2]
        over = (size + total) > jnp.int32(self.capacity)
        ins = gactive & ~over
        with jax.named_scope("repro.heap.insert"):
            if sps or spl:
                keys, vals, size, _, _, _, births, _ = heap_insert_masked(
                    keys, vals, size, gk, gv, ins,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2,
                    rider=births, oprider=gaux if spl else sp.round)
            else:
                keys, vals, size, _, _, _ = heap_insert_masked(
                    keys, vals, size, gk, gv, ins,
                    cap_log2=self.capacity_log2,
                    arity_log2=self.arity_log2)
        total = jnp.where(over, 0, total)
        telinfo = None
        if tel:
            pops = active.reshape(self.shards, self.batch).sum(
                1, dtype=jnp.int32)
            pushes = (gactive & ~over).reshape(self.shards, -1).sum(
                1, dtype=jnp.int32)         # children by generating shard
            lane = jnp.arange(sb, dtype=jnp.int32)
            mn, mx = masked_min_max(outk, lane < k)
            telinfo = (pops, pushes, jnp.broadcast_to(size, (self.shards,)),
                       mn, mx)
        if sps:
            outb_l = jnp.where(act_l, outb[rk_l], 0)
            cls = self._span_cls(outk_l, jnp.full_like(outk_l, me))
            sp = span_record(sp, cls, sp.round - outb_l, act_l, outv_l)
            sp = span_tick(sp)
        trace = (outk_l, outv_l, act_l, gk, gv, gactive)
        return (DistHeapState(keys, vals, size), acc, k, total, over,
                telinfo, sp, births, trace)

    def _broadcast_acc(self, acc):
        acc = jax.tree_util.tree_map(jnp.asarray, acc)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (self.shards,) + x.shape),
            acc)

    # -- shard_map boundary, shared by fused and legacy ---------------------
    def _unstack_round_io(self, qstate, births):
        if self.relaxed:
            k, v, sz, h = qstate
            qstate = (k[0], v[0], sz, h)
            if births is not None:
                births = births[0]
        return qstate, births

    def _restack_round_io(self, qstate, births):
        if self.relaxed:
            qstate = (qstate[0][None], qstate[1][None], qstate[2], qstate[3])
            if births is not None:
                births = births[None]
        return qstate, births


class MeshHeapEngine(_PriorityMeshBase):
    """The priority mesh megaround loop: one jitted shard_map call runs
    the whole claim → pop-min → step → push cycle for up to ``limit``
    rounds with the heap planes (per-shard in relaxed mode, replicated in
    strict mode) as loop-carried device state; the host syncs once at
    global quiescence (or every ``sync_every`` rounds).  ``run`` mirrors
    ``HeapEngine.run``: bit-deterministic, raises ``RuntimeError`` on
    heap overflow or ``max_rounds`` truncation at the next sync, and
    returns (acc, final ``DistHeapState``) — acc carries a leading shard
    axis unless ``combine`` reduces it; relaxed-mode final planes are
    stacked ``(shards, cap)``."""

    def __init__(self, step_fn: PriorityStepFn, *, mesh, axis: str = "data",
                 capacity_log2: int = 10, batch: int = 64,
                 arity_log2: int = 2, relaxed: bool = True,
                 sync_every: int = 0,
                 combine: Callable[[Any], Any] = None,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None,
                 split: bool = False) -> None:
        super().__init__(step_fn, mesh=mesh, axis=axis,
                         capacity_log2=capacity_log2, batch=batch,
                         arity_log2=arity_log2, relaxed=relaxed,
                         sync_every=sync_every, combine=combine,
                         telemetry=telemetry, spans=spans, compact=compact,
                         split=split)
        cap = self.capacity
        reg = self.registry
        # TracePlane rides replicated; the SpanPlane is sharded (each
        # shard records its own pops); the births plane matches its heap —
        # per-shard (sharded) in relaxed mode, replicated in strict mode.
        # Split mode reuses the births slot for the aux rider plane (same
        # shapes and specs).
        if relaxed:
            reg.register("heap",
                         (_sds((self.shards, cap)),) * 2, sharded=True)
            reg.register("sched", (_sds((self.shards,)),) * 2)
            self._register_obs_planes(
                self.shards, stacked=True,
                births_shape=(self.shards, cap), births_sharded=True)
            if split:
                reg.register("births", _sds((self.shards, cap)),
                             sharded=True)
            qspec = ((reg.spec("heap"),) * 2 + (reg.spec("sched"),) * 2)
        else:
            reg.register("heap", (_sds((cap,)), _sds((cap,)), _sds(())))
            self._register_obs_planes(self.shards, stacked=True,
                                      births_shape=(cap,))
            if split:
                reg.register("births", _sds((cap,)))
            qspec = reg.spec("heap")
        obs = (reg.spec("trace"), reg.spec("span"), reg.spec("births"))
        in_specs = (qspec, P(self.axis), P(), P(), P(), P()) + obs
        out_specs = (qspec, P(self.axis), P(), P(), P(), P(), P()) + obs
        self._carry_specs = in_specs
        self._megaround = jax.jit(jax.shard_map(
            self._megaround_impl, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs,
            check_vma=False))   # see the module note

    def _megaround_impl(self, qstate, acc, processed, spawned, max_occ,
                        limit, tp=None, sp=None, births=None):
        qstate, births = self._unstack_round_io(qstate, births)
        acc = _unstack(acc)
        sps = sp is not None
        if sps:   # sharded SpanPlane arrives stacked per shard
            sp = _unstack(sp)
        out = super()._megaround_impl(qstate, acc, processed, spawned,
                                      max_occ, limit, tp, sp, births)
        qstate, births_out = self._restack_round_io(out[0], out[9])
        sp_out = _restack(out[8]) if sps else out[8]
        return (qstate, _restack(out[1])) + out[2:8] + (sp_out, births_out)

    def run(self, initial_keys: np.ndarray, initial_vals: np.ndarray,
            acc: Any = None, max_rounds: int = 10_000,
            initial_aux: np.ndarray = None) -> Tuple[Any, DistHeapState]:
        """Seed the heap planes (relaxed: round-robin spray by seed rank;
        strict: one replicated heap) and run priority megarounds to
        global quiescence.  Sync contract: one host block per
        ``sync_every`` chunk (once total when 0); one psum per round on
        device.  Determinism: bit-identical to the legacy per-round
        path.  Raises ``RuntimeError`` on heap overflow or truncation at
        the next sync.  In split mode ``initial_aux`` seeds the per-item
        aux words (zeros when None)."""
        self._reset()
        ik = np.asarray(initial_keys, np.int32).reshape(-1)
        iv = np.asarray(initial_vals, np.int32).reshape(-1)
        assert ik.shape == iv.shape
        spl = self.split
        if spl:
            ia = (np.zeros_like(ik) if initial_aux is None
                  else np.asarray(initial_aux, np.int32).reshape(-1))
            assert ia.shape == ik.shape
        else:
            ia = None
        with jax.profiler.TraceAnnotation("repro.seed"):
            acc = self._broadcast_acc(acc)
            seeded = self._seed(ik, iv, ia)
            if self.relaxed:
                qstate = seeded[:4]
                occ0 = jnp.int32(int(np.asarray(qstate[2]).sum()))
                births0 = (seeded[4] if spl else
                           self._births_init((self.shards, self.capacity)))
            else:
                qstate = DistHeapState(*seeded[:3])
                occ0 = jnp.asarray(qstate.size, jnp.int32)
                births0 = (seeded[3] if spl
                           else self._births_init((self.capacity,)))
            state = [qstate, acc, jnp.int32(0), jnp.int32(0), occ0]
            ext = [self._tel_init(self.shards),
                   self._span_init(self.shards, stacked=True), births0]

        def occ_fn(q):
            return (int(np.asarray(q[2]).sum()) if self.relaxed
                    else int(np.asarray(q[2])))

        self._run_chunks(state, ext, occ_fn, "mesh heap", max_rounds)
        q = state[0]
        final = DistHeapState(q[0], q[1], q[2])
        acc = state[1]
        if self.combine is not None:
            acc = self.combine(acc)
        return acc, final


class PriorityMeshRoundRunner(_PriorityMeshBase):
    """Mesh twin of ``PriorityRoundRunner``: ``fused=True`` (default)
    delegates to ``MeshHeapEngine`` (host sync only at global
    quiescence); ``fused=False`` keeps the legacy host-driven loop — one
    jitted shard_map dispatch and one occupancy readback per round — for
    step-debug, as the parity baseline, and as the history recorder
    (``trace=True``, legacy only: per round the popped (key, val, ok)
    batches per shard and the gathered published children, the raw
    material for ``sched.plinearizability`` checking).  Both engines are
    bit-identical: same acc leaves, same heap planes, same sizes/hints
    and stats counters."""

    def __init__(self, step_fn: PriorityStepFn, *, mesh, axis: str = "data",
                 capacity_log2: int = 10, batch: int = 64,
                 arity_log2: int = 2, relaxed: bool = True,
                 fused: bool = True, sync_every: int = 0,
                 combine: Callable[[Any], Any] = None,
                 trace: bool = False,
                 telemetry: Optional[Telemetry] = None,
                 spans: Optional[Spans] = None, compact=None,
                 split: bool = False) -> None:
        super().__init__(step_fn, mesh=mesh, axis=axis,
                         capacity_log2=capacity_log2, batch=batch,
                         arity_log2=arity_log2, relaxed=relaxed,
                         sync_every=sync_every, combine=combine,
                         telemetry=telemetry, spans=spans, compact=compact,
                         split=split)
        self.fused = fused
        if trace and fused:
            raise ValueError("trace recording needs the per-round host "
                             "boundary: use fused=False")
        if spans is not None and not fused:
            raise ValueError(
                "span planes are in-loop state: spans needs the fused "
                "engine (fused=True)")
        self.trace_enabled = trace
        self.trace = []
        if fused:
            self._engine = MeshHeapEngine(
                step_fn, mesh=mesh, axis=axis, capacity_log2=capacity_log2,
                batch=batch, arity_log2=arity_log2, relaxed=relaxed,
                sync_every=sync_every, combine=combine, telemetry=telemetry,
                spans=spans, compact=compact, split=split)
            return
        self._engine = None
        sp = P(self.axis)
        hp = sp if relaxed else P()
        qspec = (hp, hp, P(), P()) if relaxed else P()
        bspec = hp if (split and relaxed) else P()
        in_specs = (qspec, bspec, sp)
        out_core = (qspec, bspec, sp, P(), P(), P())
        # trace arrays ride in the jit outputs only when recording — the
        # untraced legacy baseline must not pay per-round materialization
        # the fused engine never pays
        out_specs = out_core + ((sp, sp, sp, P(), P(), P())
                                if trace else ())
        self._round_jit = jax.jit(jax.shard_map(
            self._legacy_round, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))

    def _legacy_round(self, qstate, births, acc):
        qstate, births = self._unstack_round_io(qstate, births)
        acc = _unstack(acc)
        r = self._round(qstate, acc, births=births)
        qstate, acc, k, total, over, _, _, births = r[:8]
        qstate, births = self._restack_round_io(qstate, births)
        out = (qstate, births, _restack(acc), k, total, over)
        if self.trace_enabled:
            outk, outv, ok, gk, gv, gactive = r[8]
            out = out + (outk[None], outv[None], ok[None], gk, gv, gactive)
        return out

    def loop_carry_bytes(self, shards: int = None) -> int:
        if self._engine is not None:
            return self._engine.loop_carry_bytes(shards)
        return super().loop_carry_bytes(shards)

    def run(self, initial_keys: np.ndarray, initial_vals: np.ndarray,
            acc: Any = None, max_rounds: int = 10_000,
            initial_aux: np.ndarray = None) -> Tuple[Any, DistHeapState]:
        """Run to quiescence on the selected engine.  ``fused=True``:
        ``MeshHeapEngine.run`` contract (host sync only at quiescence /
        ``sync_every``); ``fused=False``: one dispatch and one occupancy
        readback per round (``host_syncs == rounds``), appending
        per-round pop/push records to ``self.trace`` when ``trace=True``.
        Both bit-deterministic and identical to each other; both raise on
        overflow/truncation.  In split mode ``initial_aux`` seeds the
        per-item aux words (zeros when None)."""
        if self._engine is not None:
            try:
                return self._engine.run(initial_keys, initial_vals, acc,
                                        max_rounds,
                                        initial_aux=initial_aux)
            finally:
                self.stats = dict(self._engine.stats, fused=1)
                self.sync_log = self._engine.sync_log
        self._reset()
        self.trace = []
        ik = np.asarray(initial_keys, np.int32).reshape(-1)
        iv = np.asarray(initial_vals, np.int32).reshape(-1)
        assert ik.shape == iv.shape
        spl = self.split
        if spl:
            ia = (np.zeros_like(ik) if initial_aux is None
                  else np.asarray(initial_aux, np.int32).reshape(-1))
            assert ia.shape == ik.shape
        else:
            ia = None
        acc = self._broadcast_acc(acc)
        seeded = self._seed(ik, iv, ia)
        if self.relaxed:
            qstate = seeded[:4]
            births = seeded[4] if spl else None
            occ0 = int(np.asarray(qstate[2]).sum())
        else:
            qstate = DistHeapState(*seeded[:3])
            births = seeded[3] if spl else None
            occ0 = int(np.asarray(qstate.size))

        def round_call(st, acc):
            out = self._round_jit(st[0], st[1], acc)
            q, b, acc, k, total, over = out[:6]
            return ((q, b), acc, k, total, over,
                    out[6:] if self.trace_enabled else None)

        def occ_fn(st):
            return (int(np.asarray(st[0][2]).sum()) if self.relaxed
                    else int(np.asarray(st[0][2])))

        def on_round(tr):
            if tr is None:
                return
            outk, outv, ok, gk, gv, gactive = tr
            self.trace.append({
                "pops": (np.asarray(outk), np.asarray(outv),
                         np.asarray(ok)),
                "pushes": (np.asarray(gk), np.asarray(gv),
                           np.asarray(gactive)),
            })

        st, acc = self._legacy_loop(
            (qstate, births), acc, round_call, occ0, occ_fn,
            "mesh heap", max_rounds, on_round=on_round)
        q = st[0]
        final = DistHeapState(q[0], q[1], q[2])
        if self.combine is not None:
            acc = self.combine(acc)
        return acc, final


@deprecated_engine("MeshRingEngine")
class FusedMeshRounds(MeshRingEngine):
    """Deprecated alias for ``MeshRingEngine`` (the replicated FIFO mesh
    megaround as an ``enginecore`` configuration)."""


@deprecated_engine("MeshHeapEngine")
class FusedPriorityMeshRounds(MeshHeapEngine):
    """Deprecated alias for ``MeshHeapEngine`` (the priority mesh
    megaround as an ``enginecore`` configuration)."""


# engine-matrix rows (tests/conftest.py parametrizes over these)
register_engine("mesh", MeshRoundRunner, priority=False, mesh=True)
register_engine("mesh-sharded", MeshRoundRunner, priority=False, mesh=True,
                kwargs={"sharded": True}, spans_ok=False)
register_engine("pmesh-relaxed", PriorityMeshRoundRunner, priority=True,
                mesh=True, kwargs={"relaxed": True})
register_engine("pmesh-strict", PriorityMeshRoundRunner, priority=True,
                mesh=True, kwargs={"relaxed": False})
