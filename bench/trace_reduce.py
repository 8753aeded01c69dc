"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

``extract`` reads the device operations and the harness's host spans out
of a ``jax.profiler.ProfileData``.  The functions after it work on plain
``Event`` tuples, so they are tested on intervals worked out by hand:

* ``leaves``       the operations that hold no other (a ``while`` op holds
                   its whole loop, idle gaps included);
* ``union``        the busy intervals of a device, overlaps merged;
* ``time_by``      time summed by operation name, or by scope;
* ``gaps``         the idle gaps of a device inside a window, each named by
                   the device program it fell in, or else by the innermost
                   host span that covers its midpoint;
* ``cut``          the devices whose trace the profiler cut short: their
                   plane holds as many events as it keeps.

Times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
NO_SPAN = "no host span"
# The TPU profiler keeps at most 6 * 2^20 events per device plane and drops
# later ones: every trace of a v5e cut that way held 6,291,456 events.
DEVICE_EVENT_CAP = 6 * 2 ** 20
CUT_SHARE = 0.99


class Event(NamedTuple):
    start: float
    end: float
    name: str
    scope: str = ""
    line: str = ""


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]     # device name -> its operations
    spans: List[Event]              # the harness's host spans
    modules: Dict[str, List[Event]] = {}    # device -> its program runs
    events: Dict[str, int] = {}     # device -> events its plane holds


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def op_label(hlo: str) -> str:
    """Short name of a TPU trace operation, whose event name is its HLO
    instruction: ``%fusion.97 = s32[16777216]{...} fusion(...), kind=kCustom``
    gives ``fusion.97 s32[16777216] fusion kCustom``."""
    if " = " not in hlo:
        return hlo
    name, rest = hlo.split(" = ", 1)
    if rest.startswith("("):
        shape, rest = "tuple", rest[rest.find(") ") + 2:]
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{", 1)[0]
    opcode = rest.split("(", 1)[0]
    kind = rest.split("kind=", 1)[1].split(",", 1)[0] if "kind=" in rest \
        else ""
    return " ".join(x for x in (name.lstrip("%"), shape, opcode, kind) if x)


def extract(profile, *, span_prefix: str = "bench.",
            host_ops: bool = False) -> Trace:
    """Device operations, device program runs and host spans of
    ``profile`` (a ``ProfileData``).

    Operations are the events of each device plane's ``XLA Ops`` line, named
    by ``op_label``; their scope is their HLO instruction name, which
    carries the ``jax.named_scope`` of a Pallas call
    (``repro.wavefaa.3``).  Program runs are the ``XLA Modules`` line.  With
    ``host_ops`` (a trace of the CPU backend, whose operations run on host
    threads) operations are the host events that carry an ``hlo_op``
    statistic, one device per ``device_ordinal``.  Host spans are the host
    events whose names start with ``span_prefix``."""
    ops: Dict[str, List[Event]] = defaultdict(list)
    modules: Dict[str, List[Event]] = defaultdict(list)
    spans: List[Event] = []
    events: Dict[str, int] = defaultdict(int)
    labels: Dict[str, str] = {}
    for plane in profile.planes:
        name = plane.name
        device = bool(DEVICE_PLANE.match(name))
        host = name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                events[name] += sum(1 for _ in line.events)
                continue
            if device and line.name == MODULE_LINE:
                for ev in line.events:
                    start = float(ev.start_ns)
                    modules[name].append(Event(
                        start, start + float(ev.duration_ns),
                        ev.name.split("(", 1)[0], "", line.name))
                    events[name] += 1
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                if device:
                    hlo = ev.name
                    label = labels.get(hlo)
                    if label is None:
                        label = labels[hlo] = op_label(hlo)
                    ops[name].append(Event(start, end, label,
                                           label.split(" ", 1)[0],
                                           line.name))
                    events[name] += 1
                    continue
                if ev.name.startswith(span_prefix):
                    spans.append(Event(start, end, ev.name, "", line.name))
                elif host_ops:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        dev = f"{name}:{st.get('device_ordinal', 0)}"
                        ops[dev].append(Event(start, end, ev.name,
                                              ev.name, line.name))
    for evs in list(ops.values()) + list(modules.values()):
        evs.sort()
    spans.sort()
    return Trace(dict(ops), spans, dict(modules), dict(events))


def _clip(intervals, lo, hi):
    """``(start, end)`` of each interval cut to ``[lo, hi]``, empty ones
    left out."""
    lo = float("-inf") if lo is None else lo
    hi = float("inf") if hi is None else hi
    return [(max(s, lo), min(e, hi)) for s, e, *_ in intervals
            if s < hi and e > lo and min(e, hi) > max(s, lo)]


def union(intervals: Iterable[Tuple[float, float]], lo: float = None,
          hi: float = None) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]`` where given."""
    out: List[List[float]] = []
    for s, e in sorted(_clip(intervals, lo, hi)):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(intervals, lo: float = None, hi: float = None) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(e - s for s, e in union(intervals, lo, hi))


def leaves(events: Iterable[Event]) -> List[Event]:
    """The events that hold no other event of their line: operations that
    ran, not the loops and calls around them.  A ``while`` op spans its
    whole loop, gaps between the body's operations included, so busy time
    and gaps are taken over leaves."""
    by_line: Dict[str, List[Event]] = defaultdict(list)
    for ev in events:
        by_line[ev.line].append(ev)
    out = []
    for evs in by_line.values():
        evs.sort(key=lambda ev: (ev.start, -ev.end))
        out.extend(ev for ev, nxt in zip(evs, evs[1:] + [None])
                   if nxt is None or nxt.start >= ev.end)
    out.sort()
    return out


def time_by(events: Iterable[Event], key: Callable[[Event], str],
            lo: float = None, hi: float = None) -> Dict[str, float]:
    """Time inside ``[lo, hi]`` summed by ``key(event)``; events keyed
    ``None`` are left out.  Give it leaves, or time is counted twice."""
    lo = float("-inf") if lo is None else lo
    hi = float("inf") if hi is None else hi
    acc: Dict[str, float] = defaultdict(float)
    for ev in events:
        k = key(ev)
        t = min(ev.end, hi) - max(ev.start, lo)
        if k is not None and t > 0:
            acc[k] += t
    return dict(acc)


def by_name(ev: Event) -> str:
    return ev.name


def in_scope(scope: str) -> Callable[[Event], str]:
    """Key that groups the events under ``scope`` (a name-stack part)."""
    return lambda ev: scope if scope in ev.scope else None


def gaps(events: Iterable[Event], lo: float, hi: float,
         spans: List[Event], inside: List[Event] = ()
         ) -> List[Tuple[float, float, str]]:
    """Idle gaps of a device inside ``[lo, hi]``, longest first (gaps of
    one length in time order).  A gap whose midpoint lies in one of the
    device's program runs (``inside``) is named ``inside <program>``: the
    device waited on itself between two operations of one program.  Any
    other gap takes the name of the innermost host span (latest start)
    that covers its midpoint, or ``NO_SPAN``."""
    runs = sorted(inside)
    starts = [r.start for r in runs]
    cursor, out = lo, []
    for s, e in union(events, lo, hi) + [(hi, hi)]:
        if s > cursor:
            mid = (cursor + s) / 2
            k = bisect.bisect_right(starts, mid) - 1
            if k >= 0 and mid < runs[k].end:
                label = "inside " + runs[k].name
            else:
                cover = [sp for sp in spans if sp.start <= mid < sp.end]
                label = (max(cover, key=lambda sp: (sp.start, -sp.end)).name
                         if cover else NO_SPAN)
            out.append((cursor, s, label))
        cursor = max(cursor, e)
    out.sort(key=lambda g: g[0] - g[1])
    return out


def cut(trace: Trace, devices: Iterable[str]) -> List[str]:
    """The devices whose plane holds as many events as the profiler keeps
    (``DEVICE_EVENT_CAP``): it dropped the rest, so their trace ends
    early."""
    return [d for d in devices
            if trace.events.get(d, 0) >= CUT_SHARE * DEVICE_EVENT_CAP]


def gap_time_by_label(gap_list) -> Dict[str, float]:
    acc: Dict[str, float] = defaultdict(float)
    for s, e, label in gap_list:
        acc[label] += e - s
    return dict(acc)


def top(d: Dict[str, float], k: int = 10) -> List[Tuple[str, float]]:
    return sorted(d.items(), key=lambda kv: -kv[1])[:k]
