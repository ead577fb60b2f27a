"""Reduction of a profiler trace to device busy time, idle gaps, module
and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: the device's op and module events, and the host spans that
the benchmark itself records (``TraceAnnotation``). Everything after
that works on ``Trace`` alone, so the reductions are tested on a small
recorded trace (``Trace.to_json`` / ``Trace.from_json``) without a chip.

Times are in seconds on the profiler's clock.
"""
from __future__ import annotations

import glob
import heapq
import json
import math
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

# spans the harness records around its own calls; "window" brackets the
# measured window of a traced run
HOST_SPANS = ("window", "submit", "run_chunk", "wait")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    """Device events per device plane, and the harness's host spans.

    An op event's name is its HLO instruction's name (``%fusion.12``);
    ``texts`` keeps the whole instruction of each custom call (a Pallas
    kernel is ``custom_call_target="tpu_custom_call"``), by name, so a
    kernel can be told by its operands where its name says nothing."""
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    texts: Dict[str, str] = field(default_factory=dict)

    def to_json(self) -> dict:
        def evs(es):
            return [[e.name, e.start, e.dur] for e in es]
        return {"ops": {d: evs(es) for d, es in self.ops.items()},
                "modules": {d: evs(es) for d, es in self.modules.items()},
                "host": evs(self.host), "texts": self.texts}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def evs(rows):
            return [Event(str(n), float(s), float(t)) for n, s, t in rows]
        return cls(ops={k: evs(v) for k, v in d["ops"].items()},
                   modules={k: evs(v) for k, v in d["modules"].items()},
                   host=evs(d["host"]), texts=dict(d.get("texts", {})))

    def window(self) -> Interval:
        """The traced window: the harness's ``window`` span."""
        spans = [e for e in self.host if e.name == "window"]
        if len(spans) != 1:
            raise ValueError(f"expected one 'window' span, found "
                             f"{len(spans)}")
        return spans[0].start, spans[0].end


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the directory ``jax.profiler.trace``
    wrote) into a ``Trace``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = xplane_file(path)
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = tr.ops.setdefault(plane.name, [])
                    for e in line.events:
                        text = e.name
                        name = text.split(" = ", 1)[0]
                        if "custom-call(" in text and name not in tr.texts:
                            tr.texts[name] = text
                        dst.append(Event(name, e.start_ns * 1e-9,
                                         e.duration_ns * 1e-9))
                elif line.name == MODULES_LINE:
                    tr.modules.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns * 1e-9,
                              e.duration_ns * 1e-9) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(Event(e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9)
                               for e in line.events if e.name in HOST_SPANS)
    return tr


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def clip(events: Iterable[Event], w: Interval) -> List[Interval]:
    """Event intervals cut to the window; empty ones dropped."""
    out = []
    for e in events:
        s, t = max(e.start, w[0]), min(e.end, w[1])
        if t > s:
            out.append((s, t))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def length(intervals: Iterable[Interval]) -> float:
    return sum(t - s for s, t in intervals)


def gaps(busy: List[Interval], w: Interval) -> List[Interval]:
    """The window's complement of a sorted, merged interval list."""
    out, cur = [], w[0]
    for s, t in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if w[1] > cur:
        out.append((cur, w[1]))
    return out


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------

def busy_s(tr: Trace) -> float:
    """Seconds in which some op ran, per device, averaged over the
    devices that ran any op in the window."""
    w = tr.window()
    per = [length(union(clip(es, w))) for es in tr.ops.values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else 0.0


def idle_share(tr: Trace) -> Optional[float]:
    """1 - busy / window, or None when the trace holds no device op."""
    w = tr.window()
    b = busy_s(tr)
    return None if b <= 0 else 1.0 - b / (w[1] - w[0])


def time_of(events_by_device: Dict[str, List[Event]],
            match: Callable[[str], bool], w: Interval
            ) -> Tuple[float, int]:
    """(seconds, count) of the events whose name ``match``es, cut to the
    window, summed over devices."""
    total, n = 0.0, 0
    for es in events_by_device.values():
        for e in es:
            if match(e.name):
                iv = clip([e], w)
                if iv:
                    total += length(iv)
                    n += 1
    return total, n


def self_times(events: Iterable[Event], w: Interval) -> Dict[str, float]:
    """Device time per op name, each instant given to the innermost op
    running then (the one that started last): a loop's op spans its
    whole body, and only the instants no op inside it covers are its
    own. The self times add up to the busy time, cut to the window."""
    ivs = sorted((s, t, e.name) for e in events for s, t in clip([e], w))
    acc: Dict[str, float] = {}
    by_end: List[Tuple[float, int]] = []
    by_start: List[Tuple[float, float, int]] = []
    alive = set()
    i, cur = 0, None
    while True:
        while by_end and by_end[0][0] <= cur:
            alive.discard(heapq.heappop(by_end)[1])
        nxt = min(ivs[i][0] if i < len(ivs) else math.inf,
                  by_end[0][0] if by_end else math.inf)
        if nxt == math.inf:
            break
        if alive:
            while by_start[0][2] not in alive:
                heapq.heappop(by_start)
            name = ivs[by_start[0][2]][2]
            acc[name] = acc.get(name, 0.0) + (nxt - cur)
        cur = nxt
        while i < len(ivs) and ivs[i][0] <= cur:
            s, t, _ = ivs[i]
            heapq.heappush(by_end, (t, i))
            heapq.heappush(by_start, (-s, t, i))
            alive.add(i)
            i += 1
    return acc


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` op names with the most device self time in the window."""
    w = tr.window()
    acc: Dict[str, float] = {}
    for es in tr.ops.values():
        for k, v in self_times(es, w).items():
            acc[k] = acc.get(k, 0.0) + v
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of the first device in the window, each
    named by the host span that overlaps it most (``none`` if no span
    does)."""
    w = tr.window()
    if not tr.ops:
        return []
    dev = sorted(tr.ops)[0]
    busy = union(clip(tr.ops[dev], w))
    spans = [e for e in tr.host if e.name != "window"]
    out = []
    for s, t in sorted(gaps(busy, w), key=lambda g: g[0] - g[1])[:n]:
        best, name = 0.0, "none"
        for e in spans:
            ov = min(t, e.end) - max(s, e.start)
            if ov > best:
                best, name = ov, e.name
        out.append([name, t - s])
    return out


def write_json(tr: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)


def read_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
