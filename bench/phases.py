"""The phases of a round on the device trace.

The round engines run each phase of a round under a named scope
(``runtime/enginecore.py``): ``repro.ring.deq``, ``repro.ring.enq``,
``repro.heap.pop``, ``repro.heap.insert``, ``repro.step``,
``repro.publish``, and ``repro.wavefaa`` for the one Pallas call.  A scope
does not rename an XLA operation: the trace still says ``fusion.97``.  It
lives only in the ``op_name`` metadata of the compiled instruction, and a
fusion carries its root op's.  So the scope path of a trace operation is
the ``op_name`` of the instruction of its name (``Event.scope``) in the
optimized text of the megaround that ran it, which
``EngineCore.megaround_hlo`` gives.  This is the one source on every
backend: the CPU trace carries no ``op_name``, and the trace reduction
keeps no statistic of a device operation.

``megaround_text`` builds the cell's program once more after the window
(a ``--trace 1`` run only, so ``setup_s`` never holds it), warms it, and
asks its engine for the text; a program whose engine has no
``megaround_hlo`` gives ``None``, and every metric read from phases then
reads nothing.  The functions after it work on plain ``Event`` tuples and
are tested on intervals worked out by hand:

* ``op_paths``     instruction name -> ``op_name``, from compiled text;
* ``phase``        the innermost ``repro.`` scope of a path;
* ``runs``         a device's program runs: the trace's ``XLA Modules``
                   line, or, on a trace that has none (the CPU's), the
                   operations no other operation holds;
* ``scoped_gaps``  the idle gaps inside program runs, each with the
                   operations on both sides of it.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

from . import manifest as mf
from . import trace_reduce as tr

SCOPE = "repro."
RING = ("repro.ring.deq", "repro.ring.enq")
HEAP = ("repro.heap.pop", "repro.heap.insert")
STEP = "repro.step"
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([^\s,}]+)")
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)


class Phases(NamedTuple):
    """The fullest device's leaves with their phase (``None``: no
    ``repro.`` scope, or outside the megaround), its program runs, and
    the megaround's module name."""
    leaves: List[tr.Event]
    phase: List[Optional[str]]
    runs: List[tr.Event]
    module: str


def op_paths(hlo: str) -> Tuple[str, Dict[str, str]]:
    """``(module name, instruction name -> op_name)`` of an optimized HLO
    module's text.  An instruction with no ``op_name`` of its own (a copy
    or a wrapping fusion a late pass added) takes the one of the root of
    the computation it calls, else the one of the instruction that calls
    the computation it lies in (the loop whose carry a copy moves)."""
    m = _MODULE.search(hlo)
    paths: Dict[str, str] = {}
    calls: Dict[str, str] = {}       # instruction -> a computation it calls
    caller: Dict[str, str] = {}      # computation -> the instruction calling it
    home: Dict[str, str] = {}        # instruction -> its computation
    roots: Dict[str, str] = {}       # computation -> its root instruction
    comp = ""
    for line in hlo.splitlines():
        ins = _INSTR.match(line)
        if ins is None:
            if line.rstrip().endswith("{"):
                comp = line.split()[1 if line.startswith("ENTRY") else 0]
                comp = comp.lstrip("%")
            continue
        root, name, rest = ins.groups()
        home[name] = comp
        if root:
            roots[comp] = name
        op = _OP_NAME.search(rest)
        if op:
            paths[name] = op.group(1)
        for called in _CALLS.findall(rest):
            calls.setdefault(name, called)
            caller.setdefault(called, name)

    def resolve(name: str, depth: int = 0) -> Optional[str]:
        if name in paths or depth > 64:
            return paths.get(name)
        inner = roots.get(calls.get(name, ""))
        if inner in paths:
            return paths[inner]
        outer = caller.get(home.get(name, ""))
        return resolve(outer, depth + 1) if outer else None

    for name in home:
        if name not in paths:
            p = resolve(name)
            if p is not None:
                paths[name] = p
    return (m.group(1) if m else ""), paths


def phase(path: Optional[str]) -> Optional[str]:
    """The innermost ``repro.`` scope of a scope path: ``.../repro.publish/
    .../repro.ring.enq/scatter`` is in ``repro.ring.enq``."""
    if not path:
        return None
    inner = [s for s in path.split("/") if s.startswith(SCOPE)]
    return inner[-1] if inner else None


def runs(trace: tr.Trace, device: str) -> List[tr.Event]:
    """The device's program runs: its ``XLA Modules`` events, or, where
    the trace has none (the CPU backend's), its outermost operations."""
    mods = trace.modules.get(device)
    if mods:
        return sorted(mods)
    out: List[tr.Event] = []
    by_line: Dict[str, List[tr.Event]] = {}
    for ev in trace.ops.get(device, ()):
        by_line.setdefault(ev.line, []).append(ev)
    for evs in by_line.values():
        evs.sort(key=lambda ev: (ev.start, -ev.end))
        end = float("-inf")
        for ev in evs:
            if ev.start >= end:
                out.append(ev)
                end = ev.end
            else:
                end = max(end, ev.end)
    return sorted(out)


def _run_of(run_list: List[tr.Event], starts: List[float], t: float):
    k = bisect.bisect_right(starts, t) - 1
    return run_list[k] if k >= 0 and t < run_list[k].end else None


def scoped_gaps(events, lo: float, hi: float, run_list: List[tr.Event]
                ) -> List[Tuple[float, float, tr.Event, tr.Event]]:
    """Idle gaps of a device inside ``[lo, hi]`` whose midpoint lies in
    one of ``run_list``, in time order, each as ``(start, end, before,
    after)``: the operation that ended last before the gap and the one
    that starts it.  A gap at either end of the window, with no operation
    on one side, is left out.  Give it leaves."""
    run_list = sorted(run_list)
    starts = [r.start for r in run_list]
    out = []
    last = None
    for ev in sorted(events, key=lambda ev: (ev.start, ev.end)):
        if ev.start >= hi:
            break
        if last is not None and ev.start > last.end:
            s, e = max(last.end, lo), min(ev.start, hi)
            if e > s and _run_of(run_list, starts, (s + e) / 2) is not None:
                out.append((s, e, last, ev))
        if last is None or ev.end > last.end:
            last = ev
    return out


_TEXTS: Dict[str, Optional[str]] = {}


def megaround_text(cell: mf.Cell) -> Optional[str]:
    """The optimized text of the cell's megaround, from the cell's program
    built and warmed once more (cached per cell and size); ``None`` when
    the program's engine cannot give it."""
    key = json.dumps([cell.name, cell.spec, cell.config], sort_keys=True)
    if key not in _TEXTS:
        import jax
        query = mf.query_module(cell.spec["query"]).Query(
            cell, 0, jax.devices()[:cell.spec["chips"]])
        text = None
        if hasattr(query.engine, "megaround_hlo"):
            query.warm()
            text = query.engine.megaround_hlo()
        query.release()
        _TEXTS[key] = text
    return _TEXTS[key]


_LAST: list = [None, None]      # the last trace context and its phases


def of(ctx, log=sys.stderr) -> Optional[Phases]:
    """The phases of the fullest device's leaves in the traced window, or
    ``None`` when the program names none.  The first call for a run
    prints on ``log`` how busy time divides by phase, and the shares
    under no ``repro.`` scope: outside the megaround, and of operations
    whose names the megaround's text lacks (there, a compile that named
    its operations otherwise than the traced one would show)."""
    if _LAST[0] is ctx:
        return _LAST[1]
    text = megaround_text(ctx.cell)
    result = None
    if text is not None:
        module, paths = op_paths(text)
        dev = ctx.fullest
        run_list = runs(ctx.trace, dev)
        named = bool(ctx.trace.modules.get(dev))
        starts = [r.start for r in run_list]
        leaves = ctx.leaves[dev]
        names, other, unknown = [], [], []
        for ev in leaves:
            run = _run_of(run_list, starts, ev.start) if named else None
            if named and (run is None or run.name != module):
                names.append(None)
                other.append(ev)
                continue
            if ev.scope not in paths:
                unknown.append(ev)
            names.append(phase(paths.get(ev.scope)))
        if any(names):
            result = Phases(leaves, names, run_list, module)
            _log(ctx, result, other, unknown, log)
    _LAST[:] = [ctx, result]
    return result


def busy_by_phase(ctx, ph: Phases) -> Dict[Optional[str], float]:
    """Busy time (ns) of the fullest device inside ``[lo, hi]`` by phase;
    ``None`` collects the operations under no ``repro.`` scope."""
    acc: Dict[Optional[str], float] = {}
    for ev, p in zip(ph.leaves, ph.phase):
        t = min(ev.end, ctx.hi) - max(ev.start, ctx.lo)
        if t > 0:
            acc[p] = acc.get(p, 0.0) + t
    return acc


def share_of_busy(ctx, names) -> Optional[float]:
    """Percent of the fullest device's busy time under the phases
    ``names``, or ``None`` when the program names no phase."""
    ph = of(ctx)
    busy = ctx.busy[ctx.fullest]
    if ph is None or not busy:
        return None
    t = sum(v for k, v in busy_by_phase(ctx, ph).items() if k in names)
    return 100.0 * t / busy


def _log(ctx, ph: Phases, other, unknown, log) -> None:
    busy = ctx.busy[ctx.fullest] or 1.0
    by = busy_by_phase(ctx, ph)

    def pct(t):
        return f"{100.0 * t / busy:.3f}%"

    def span(evs):
        return tr.busy(evs, ctx.lo, ctx.hi)

    parts = ", ".join(f"{k} {pct(v)}" for k, v in
                      sorted(by.items(), key=lambda kv: -kv[1])
                      if k is not None)
    print(f"phases on {ctx.fullest} ({ph.module}): {parts}; under no "
          f"{SCOPE} scope {pct(by.get(None, 0.0))} of busy time, of which "
          f"outside the megaround {pct(span(other))}, not in its text "
          f"{pct(span(unknown))}", file=log)
