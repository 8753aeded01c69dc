"""The fused-engine core (DESIGN.md § 4.8): ONE loop builder, ONE plane
registry, ONE host driver behind every round engine.

Every fused engine in this repo — chip FIFO, chip priority, mesh FIFO
(replicated or sharded rings), mesh priority (relaxed or strict) — runs
the same shape of computation: a jitted ``lax.while_loop`` whose body is
one *round* (claim → step → publish) over loop-carried queue planes,
with optional trace/span planes riding the carry, chunked by the host
driver at ``sync_every`` and raising on overflow/truncation at the next
sync.  Before this module each engine hand-threaded that shape — four
copies of the carry plumbing, four copies of the chunk driver, two
copies of the legacy per-round loop.  Now an engine is a *configuration*:

* ``_round(qstate, acc, tel=, sp=, births=)`` — the one-round body.
  Contract: returns ``(qstate, acc, k, total, over, telinfo, sp,
  births[, extra...])`` where ``k`` is the round's claim count, ``total``
  the installed-children count (already zeroed when ``over``), ``over``
  the traced overflow flag, and ``telinfo`` a ``(pops, pushes, occs,
  min, max)`` record tuple (``None`` when ``tel`` is off).  Span
  record/tick happen inside the round; trailing ``extra`` entries (the
  legacy trace tuple) are ignored by the fused loop.
* ``_occ_of(qstate)`` — the traced occupancy (the loop condition and the
  ``max_occupancy`` counter read it).
* a ``PlaneRegistry`` describing the loop carry: named plane groups with
  a sharded/replicated flag each, from which the engine derives its
  shard_map specs AND its per-shard loop-carry byte count (the
  O(ring/shards) claim ``benchmarks/bench_mesh.py`` measures).

``fused_loop`` assembles the while_loop from ``_round``/``_occ_of``;
``_run_chunks`` drives the standardized megaround signature
``megaround(qstate, acc, processed, spawned, max_occ, limit, tp, sp,
births)`` chunk by chunk; ``_legacy_loop`` is the shared host-driven
per-round baseline.  Bit-identity rule: the builder performs exactly the
carry updates the hand-rolled loops performed, in pure-functional order,
so an engine moved onto the core is bit-identical to its pre-core twin
(asserted against recorded goldens in ``tests/test_enginecore.py``).

Drain ordering at each host sync is fixed by the driver: trace plane
first (``Telemetry.drain`` → ``heartbeat`` → ``finish``), span plane
second (``Spans.drain`` → ``finish``) — registered once here, never
re-threaded per engine.

Phase names on the profiler's clock.  Every round body runs its phases
under one vocabulary of ``jax.named_scope`` names, whatever kernel
implements them: ``repro.ring.deq`` / ``repro.ring.enq`` (the ring
waves), ``repro.heap.pop`` (with the claim schedule) /
``repro.heap.insert`` (the heap waves), ``repro.step`` (the step
function) and ``repro.publish`` (the mesh engines' one-psum exchange);
``repro.wavefaa`` keeps its own scope, which names its Pallas call.  A
scope reaches a compiled op only as its ``op_name`` metadata (a fusion
carries its root op's), so ``megaround_hlo`` gives the compiled text that
maps a trace's op names to phases.  The host steps of a run are
``jax.profiler.TraceAnnotation`` spans: ``repro.seed`` (building and
placing the seed carry), ``repro.dispatch`` (the megaround call),
``repro.sync`` (the occupancy readback that waits for it) and
``repro.drain`` (the trace/span drains, when a collector is on).  Both
add metadata and host annotations only: the loop carry, the ops and the
results are those of the unnamed loop.
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels.ring_slots import SPAN_ROUND_CAP
from ..obs.spans import Spans, span_init
from ..obs.trace import SyncPoint, Telemetry, trace_init, trace_record


def _sds(shape, dtype=jnp.int32):
    """Shape-only leaf for registry declarations (no device allocation)."""
    return jax.ShapeDtypeStruct(shape, dtype)


def _aval(x):
    """Shape, dtype and placement of a megaround argument, for lowering
    the same program again without holding its buffers."""
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


class PlaneGroup(NamedTuple):
    """One named group of loop-carried leaves (a queue plane set, the
    trace plane, the span plane, a stamp plane...)."""
    name: str
    shapes: Tuple[Tuple[Tuple[int, ...], str], ...]   # ((shape, dtype), ...)
    sharded: bool

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(s, dtype=np.int64)) * np.dtype(d).itemsize
                   for s, d in self.shapes)


class PlaneRegistry:
    """The loop-carry plane registry: each engine registers its carried
    plane groups ONCE (name + leaves + sharded flag), and the registry
    answers the two questions previously hand-threaded through every
    engine: which shard_map spec each group rides (``spec``/
    ``leaf_specs``), and how many bytes of loop carry a shard actually
    holds (``bytes_per_shard`` — sharded groups divide by the shard
    count, replicated groups don't).  Shapes registered for sharded
    groups are GLOBAL (stacked ``(shards, ...)``), matching what the
    host passes the jitted megaround."""

    def __init__(self, axis: Optional[str] = None) -> None:
        self.axis = axis
        self._groups: Dict[str, PlaneGroup] = {}

    def register(self, name: str, example, *, sharded: bool = False) -> None:
        leaves = jax.tree_util.tree_leaves(example)
        shapes = tuple((tuple(int(d) for d in leaf.shape),
                        jnp.dtype(leaf.dtype).name) for leaf in leaves)
        self._groups[name] = PlaneGroup(name, shapes, sharded)

    @property
    def groups(self) -> Tuple[PlaneGroup, ...]:
        return tuple(self._groups.values())

    def spec(self, name: str):
        """One pytree-prefix spec for the whole group (``P(axis)`` when
        sharded, ``P()`` when replicated)."""
        g = self._groups[name]
        return P(self.axis) if (g.sharded and self.axis) else P()

    def leaf_specs(self, *names: str) -> tuple:
        """Per-leaf specs for groups whose leaves travel as separate
        megaround arguments."""
        out = []
        for nm in names:
            s = self.spec(nm)
            out.extend([s] * len(self._groups[nm].shapes))
        return tuple(out)

    def bytes_per_shard(self, shards: int = 1) -> int:
        total = 0
        for g in self._groups.values():
            total += g.nbytes // shards if g.sharded else g.nbytes
        return total


class EngineEntry(NamedTuple):
    """One row of the engine matrix (``ENGINE_REGISTRY``): enough for the
    parametrized test/bench harnesses to build and drive the runner."""
    name: str
    runner: type
    priority: bool          # PriorityStepFn + run(keys, vals) signature
    mesh: bool              # constructor takes mesh=
    kwargs: Dict[str, Any]  # mode selectors (relaxed=, sharded=, ...)
    spans_ok: bool          # span planes supported in this configuration


ENGINE_REGISTRY: Dict[str, EngineEntry] = {}


def register_engine(name: str, runner: type, *, priority: bool, mesh: bool,
                    kwargs: Optional[Dict[str, Any]] = None,
                    spans_ok: bool = True) -> None:
    """Register a runner configuration in the engine matrix.  New engines
    self-register at import; the parity/telemetry-off test suite and the
    bench harness enumerate the matrix instead of hand-copying per-engine
    cases (tests/conftest.py)."""
    ENGINE_REGISTRY[name] = EngineEntry(name, runner, priority, mesh,
                                        dict(kwargs or {}), spans_ok)


class EngineCore:
    """Shared core of every fused round engine: the while_loop builder
    (``fused_loop``), the chunked host driver (``_run_chunks`` /
    ``_drive``), the legacy per-round baseline (``_legacy_loop``), the
    obs-plane lifecycle (init memoization + drain hooks), and the plane
    registry.  Subclasses configure ``_round`` / ``_occ_of`` / specs.

    Telemetry (DESIGN.md § 7): when constructed with a
    ``repro.obs.Telemetry``, the megaround carries a ``TracePlane`` of
    per-round records as extra loop state; the driver drains it into the
    collector at every host sync (the same sync — telemetry adds zero
    extra syncs).  With ``telemetry=None`` the plane never enters the
    carry and the jitted loop is the exact pre-telemetry graph
    (bit-identity asserted in tests).  Spans ride the same way
    (DESIGN.md § 7.6), with one extra driver duty: the packed
    ``(birth << 1) | 1`` stamp format caps the round clock at 2^30
    (``kernels.ring_slots.SPAN_ROUND_CAP``), so the driver clamps each
    chunk's limit to the cap and raises instead of letting stamps wrap."""

    sync_every: int
    capacity: int
    telemetry: Optional[Telemetry]
    spans: Optional[Spans] = None
    span_round_cap: int = SPAN_ROUND_CAP
    # optional extra loop-exit predicate ``carry -> bool`` (python-level:
    # when None — every engine except the serving admission tick — the
    # built graph is byte-identical to the hookless loop, so the recorded
    # goldens keep holding).  The predicate MUST be replicated across
    # shards: the relaxed round's publish psum is a collective, and a
    # shard exiting early would deadlock the others.
    _extra_cond = None
    # mesh engines: the shard_map in_specs of the megaround, from which the
    # chunk loop places the initial carry on the mesh (None = chip engine)
    _carry_specs = None
    # argument avals of the last megaround call (``megaround_hlo``)
    _megaround_avals = None

    def _reset(self) -> None:
        self.stats: Dict[str, int] = {}
        self.sync_log: List[SyncPoint] = []
        if self.telemetry is not None:
            self.telemetry.begin_run()
        if self.spans is not None:
            self.spans.begin_run()

    # -- plane registry ------------------------------------------------------

    @property
    def registry(self) -> PlaneRegistry:
        if getattr(self, "_registry", None) is None:
            self._registry = PlaneRegistry(getattr(self, "axis", None))
        return self._registry

    def _register_obs_planes(self, shards: int = 1, *, stacked: bool = False,
                             births_shape=None,
                             births_sharded: bool = False) -> None:
        """Register the trace/span/births carry groups (empty groups when
        the corresponding collector is off, so specs stay derivable)."""
        reg = self.registry
        reg.register("trace", self._tel_init(shards))
        reg.register("span", self._span_init(shards, stacked=stacked),
                     sharded=stacked)
        births = None
        if self.spans is not None and births_shape is not None:
            births = _sds(births_shape)
        reg.register("births", births, sharded=births_sharded)

    def loop_carry_bytes(self, shards: Optional[int] = None) -> int:
        """Per-shard bytes of registered loop carry (queue planes + obs
        planes; the workload's acc is excluded — it is the caller's
        state, not the engine's).  This is the measured column behind
        the sharded ring's O(ring/shards) claim (bench_mesh)."""
        return self.registry.bytes_per_shard(
            shards if shards is not None else getattr(self, "shards", 1))

    # -- obs plane lifecycle (memoized zero-init, DESIGN.md § 7.5/7.6) -------

    def _tel_init(self, shards: int = 1):
        """Fresh plane for one run (telemetry on), else None.  The zero
        plane is immutable (recording is functional), so one instance is
        memoized and shared across runs — plane init must not show up in
        the per-run overhead budget (DESIGN.md § 7.5)."""
        if self.telemetry is None:
            return None
        key = (self.telemetry.capacity, shards)
        if getattr(self, "_tel_zero_key", None) != key:
            self._tel_zero = trace_init(*key)
            self._tel_zero_key = key
        return self._tel_zero

    def _span_init(self, shards: int = 1, *, stacked: bool = False):
        """Fresh SpanPlane for one run (spans on), else None — memoized
        like ``_tel_init`` (same zero-init budget rule, DESIGN.md § 7.6).
        ``stacked=True`` (the mesh engines) broadcasts a leading shard
        axis for ``P(axis)``-sharded planes; with no ``class_of`` the
        mesh histogram defaults to one row per shard."""
        if self.spans is None:
            return None
        rows = self.spans.classes
        if stacked and self.spans.class_of is None:
            rows = shards
        key = (rows, self.spans.buckets, self.spans.flow_capacity,
               shards if stacked else 0, self.batch)
        if getattr(self, "_span_zero_key", None) != key:
            z = span_init(rows, buckets=self.spans.buckets,
                          flow_capacity=self.spans.flow_capacity,
                          lanes=self.batch)
            if stacked:
                z = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (shards,) + x.shape),
                    z)
            self._span_zero = z
            self._span_zero_key = key
        return self._span_zero

    def _births_init(self, shape):
        """Fresh zeroed birth-stamp plane (spans on), else None — memoized;
        zero stamps make seed items born at round 0 by construction."""
        if self.spans is None:
            return None
        if getattr(self, "_births_zero_shape", None) != shape:
            self._births_zero = jnp.zeros(shape, jnp.int32)
            self._births_zero_shape = shape
        return self._births_zero

    def _span_cls(self, keys_or_vals, default):
        """Per-lane class row: the collector's ``class_of`` applied to the
        popped keys (priority) / payloads (FIFO), else ``default``."""
        if self.spans is not None and self.spans.class_of is not None:
            return jnp.asarray(self.spans.class_of(keys_or_vals), jnp.int32)
        return default

    def _tel_plane(self):
        """Current TracePlane from the chunk state (``_run_chunks``
        installs the accessor)."""
        raise NotImplementedError

    def _span_plane(self):
        """Current SpanPlane from the chunk state (``_run_chunks``
        installs the accessor)."""
        raise NotImplementedError

    # -- the ONE fused loop builder ------------------------------------------

    def fused_loop(self, round_fn, occ_of, qstate, acc, processed, spawned,
                   max_occ, limit, tp, sp, births):
        """Build and run the jitted megaround ``lax.while_loop`` over one
        engine's round body.  ``round_fn`` follows the ``_round`` contract
        (module docstring); ``occ_of`` maps the queue state to its traced
        occupancy.  Carry layout (and return):

            (qstate, acc, processed, spawned, max_occ, oflow, rounds,
             tp, sp, births)

        ``tp``/``sp``/``births`` slots are ``None`` pytrees when the
        corresponding collector is off, so the default call compiles to
        the exact unobserved graph — every obs branch here is
        python-level.  The counter updates are exactly the hand-rolled
        engines' updates (bit-identity rule, tests/test_enginecore.py)."""
        tel = tp is not None

        def body(carry):
            (qstate, acc, processed, spawned, max_occ, oflow, rounds,
             tp, sp, births) = carry
            r = round_fn(qstate, acc, tel=tel, sp=sp, births=births)
            qstate, acc, k, total, over, telinfo, sp, births = r[:8]
            if tel:
                pops, pushes, occs, mn, mx = telinfo
                tp = trace_record(tp, tp.count, pops, pushes, occs,
                                  mn, mx, over)
            return (qstate, acc, processed + k, spawned + total,
                    jnp.maximum(max_occ, occ_of(qstate)), oflow | over,
                    rounds + 1, tp, sp, births)

        def cond(carry):
            c = ((occ_of(carry[0]) > 0) & (~carry[5])
                 & (carry[6] < limit))
            if self._extra_cond is not None:
                c = c & self._extra_cond(carry)
            return c

        return jax.lax.while_loop(cond, body, (
            qstate, acc, processed, spawned, max_occ, jnp.bool_(False),
            jnp.int32(0), tp, sp, births))

    def _megaround_impl(self, qstate, acc, processed, spawned, max_occ,
                        limit, tp=None, sp=None, births=None):
        """Default megaround: the fused loop over this engine's round.
        Mesh engines wrap this to unstack/restack their ``P(axis)``
        leaves at the shard_map boundary."""
        return self.fused_loop(self._round, self._occ_of, qstate, acc,
                               processed, spawned, max_occ, limit,
                               tp, sp, births)

    # -- host drivers --------------------------------------------------------

    def _run_chunks(self, state, ext, occ_fn, what: str,
                    max_rounds: int) -> None:
        """Drive the standardized megaround to quiescence.  ``state`` =
        ``[qstate, acc, processed, spawned, max_occ]`` (mutated in
        place), ``ext`` = ``[tp, sp, births]``; ``occ_fn(qstate)`` is the
        ONE host-sync readback per chunk."""
        self._tel_plane = lambda: ext[0]
        self._span_plane = lambda: ext[1]
        specs = self._carry_specs
        if specs is not None:
            # mesh engines: the host-built seed planes and acc are placed
            # with the mesh shardings once; every later chunk takes the
            # megaround's own (already sharded) outputs
            shard = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                tuple(specs[:5]) + tuple(specs[6:]),
                is_leaf=lambda s: isinstance(s, P))
            with jax.profiler.TraceAnnotation("repro.seed"):
                placed = jax.device_put(tuple(state) + tuple(ext), shard)
            state[:], ext[:] = placed[:5], placed[5:]

        def chunk_fn(limit):
            args = (*state, jnp.int32(limit), *ext)
            self._megaround_avals = jax.tree_util.tree_map(_aval, args)
            with jax.profiler.TraceAnnotation("repro.dispatch"):
                out = self._megaround(*args)
            state[:] = out[:5]
            oflow, r = out[5], out[6]
            ext[:] = out[7:]
            with jax.profiler.TraceAnnotation("repro.sync"):
                occ = occ_fn(state[0])          # THE host sync
                return (occ, int(r), bool(oflow), int(state[2]),
                        int(state[3]), int(state[4]))

        self._drive(chunk_fn, max_rounds, what)

    def megaround_hlo(self) -> str:
        """The optimized HLO text of the megaround as the last chunk
        called it: the instruction names a device trace shows, each with
        the ``op_name`` metadata that holds its phase scope (module
        docstring).  Compiles the program once more (a read of the
        persistent compilation cache, where that is on), so call it
        outside any timed window.  Raises ``RuntimeError`` before the
        engine's first run."""
        if self._megaround_avals is None:
            raise RuntimeError("no megaround has run on this engine yet")
        return self._megaround.lower(*self._megaround_avals).compile(
        ).as_text()

    def _drive(self, chunk_fn, max_rounds: int, what: str) -> None:
        """``chunk_fn(limit)`` advances internal state by up to ``limit``
        rounds and returns (occupancy, rounds_delta, overflow, processed,
        spawned, max_occ) — one host sync per call."""
        chunk = self.sync_every if self.sync_every > 0 else max_rounds
        rounds = host_syncs = 0
        while True:
            limit = min(chunk, max_rounds - rounds)
            if self.spans is not None:
                # stamp-time cap enforcement: no round past the cap ever
                # writes a packed birth stamp (the stamps would wrap)
                limit = min(limit, self.span_round_cap - rounds)
            occ, r, oflow, processed, spawned, max_occ = chunk_fn(limit)
            rounds += r
            host_syncs += 1
            now = time.time()
            point = SyncPoint(rounds=rounds, occupancy=occ, wall_time=now,
                              host_syncs=host_syncs)
            self.sync_log.append(point)
            self.stats = {
                "rounds": rounds, "processed": processed, "spawned": spawned,
                "max_occupancy": max_occ, "drained": int(occ == 0),
                "host_syncs": host_syncs,
            }
            if self.telemetry is not None:
                with jax.profiler.TraceAnnotation("repro.drain"):
                    self.telemetry.drain(self._tel_plane(),
                                         sync=host_syncs - 1, wall_time=now)
                    self.telemetry.heartbeat(point)
                    self.telemetry.finish(self.stats)
            if self.spans is not None:
                with jax.profiler.TraceAnnotation("repro.drain"):
                    self.spans.drain(self._span_plane(), wall_time=now)
                    self.spans.finish(self.stats)
            if oflow:
                raise RuntimeError(
                    f"{what} overflow: occupancy {occ} + spawned children "
                    f"exceed capacity {self.capacity} at round {rounds} "
                    f"(raise capacity_log2 or lower the fanout)")
            if occ == 0:
                return
            if self.spans is not None and rounds >= self.span_round_cap:
                raise RuntimeError(
                    f"{what} span round clock reached the packed "
                    f"birth-stamp cap ({self.span_round_cap} rounds) with "
                    f"occupancy {occ}: stamps would wrap the "
                    f"(birth << 1) | 1 flag plane (run without spans or "
                    f"split the run)")
            if rounds >= max_rounds:
                raise RuntimeError(
                    f"{what} round loop truncated at max_rounds="
                    f"{max_rounds} with occupancy {occ}: not quiescent "
                    f"(stats['drained']=0)")

    def _legacy_loop(self, state, acc, round_call, occ0: int, occ_fn,
                     what: str, max_rounds: int, on_round=None):
        """The host-driven per-round baseline (one jitted dispatch + one
        occupancy readback per round, ``host_syncs == rounds``), shared
        by the legacy mesh runners.  ``round_call(state, acc)`` returns
        ``(state, acc, k, total, over, extra)``; ``on_round(extra)``
        fires per round (the priority trace recorder).  Returns
        ``(state, acc)``; raises the engine's overflow/truncation errors
        with its ``what`` wording."""
        rounds = processed = spawned = host_syncs = 0
        occ = max_occ = occ0
        overflow = False
        while occ > 0 and rounds < max_rounds:
            state, acc, k, total, over, extra = round_call(state, acc)
            occ = occ_fn(state)
            host_syncs += 1                     # per-round readback
            rounds += 1
            processed += int(k)
            spawned += int(total)
            max_occ = max(max_occ, occ)
            self.sync_log.append(SyncPoint(
                rounds=rounds, occupancy=occ, wall_time=time.time(),
                host_syncs=host_syncs))
            if on_round is not None:
                on_round(extra)
            if bool(over):
                overflow = True
                break
        self.stats = {"rounds": rounds, "processed": processed,
                      "spawned": spawned, "max_occupancy": max_occ,
                      "drained": int(occ == 0),
                      "host_syncs": host_syncs, "fused": 0}
        if overflow:
            raise RuntimeError(
                f"{what} overflow: occupancy {occ} + spawned children "
                f"exceed capacity {self.capacity} at round {rounds} (raise "
                f"capacity_log2 or lower the fanout)")
        if occ > 0:
            raise RuntimeError(
                f"{what} round loop truncated at max_rounds={max_rounds} "
                f"with occupancy {occ}: not quiescent "
                f"(stats['drained']=0)")
        return state, acc


def deprecated_engine(new_name: str):
    """Class decorator for the legacy ``Fused*`` entry points: identical
    constructor signature and behavior (a subclass), plus a
    ``DeprecationWarning`` naming the core configuration to use."""
    def wrap(cls):
        base = cls.__mro__[1]

        def __init__(self, *args, **kwargs):
            warnings.warn(
                f"{cls.__name__} is deprecated: use {new_name} (the four "
                f"round loops are unified behind runtime.enginecore)",
                DeprecationWarning, stacklevel=2)
            base.__init__(self, *args, **kwargs)

        cls.__init__ = __init__
        return cls
    return wrap
