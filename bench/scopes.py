"""Device time by primitive, and idle time by program span, from a
profiler trace.

The chunk program runs each localization primitive under a named scope
of its registry name (``core/step.py``), so every device op's
``op_name`` metadata holds the path of scopes it was traced under, such
as ``jit(<unknown>)/while/body/closed_call/vmap(frontend)/add``. The
serving engine marks its host spans in the trace (``engine.*``,
``fleet.*``, ``pool.*``; ``repro/serve/spans.py``).

``load`` reads what ``devtrace.load`` reads and keeps, besides, each
device op's scope path (``Scoped.scopes``, in the order of
``Trace.ops``) and the program's spans (``Scoped.program``). A v5e
trace gives an op event no ``op_name`` (its stats are its offset,
duration and a time scale), so the path is looked up by the op's name
in the compiled HLO text handed to ``load``: the
``metadata={op_name=...}`` of each instruction.

``primitive_self_times`` reuses ``devtrace.self_times``: each instant
goes to the innermost op running then, and is booked to the primitive
that op's scope names, or to ``unscoped`` (the scan, the per-slot
select, state glue) where it names none.

Times are in seconds on the profiler's clock.
"""
from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Mapping, Optional

import devtrace
from devtrace import Event, Interval

# the registry's primitive names (``repro.core.primitives``)
PRIMITIVES = ("frontend", "track_ring", "imu_propagate", "msckf_update",
              "gps_fusion", "map_query", "bow_histogram", "ba_marginalize")
UNSCOPED = "unscoped"
PROGRAM_PREFIXES = ("engine.", "fleet.", "pool.")
CHUNK_MODULE = "jit__unknown("

_WORD = re.compile(r"[A-Za-z_]\w*")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?(%[^\s=]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)


@dataclass
class Scoped(devtrace.Trace):
    """A ``devtrace.Trace`` with each device op's scope path
    (``scopes[device][i]`` belongs to ``ops[device][i]``; "" when
    unknown) and the program's own host spans."""
    scopes: Dict[str, List[str]] = field(default_factory=dict)
    program: List[Event] = field(default_factory=list)

    def to_json(self) -> dict:
        d = super().to_json()
        d["scopes"] = self.scopes
        d["program"] = [[e.name, e.start, e.dur] for e in self.program]
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Scoped":
        base = devtrace.Trace.from_json(d)
        return cls(ops=base.ops, modules=base.modules, host=base.host,
                   texts=base.texts,
                   scopes={k: list(v) for k, v in d["scopes"].items()},
                   program=[Event(str(n), float(s), float(t))
                            for n, s, t in d["program"]])


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` path, from compiled HLO text."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(text)}


def load(path: str, hlo_texts: Iterable[str] = ()) -> Scoped:
    """Read an ``.xplane.pb`` (or the directory ``jax.profiler`` wrote)
    into a ``Scoped`` trace; ``hlo_texts`` is the compiled HLO of the
    programs whose ops get a scope (others get "")."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = devtrace.xplane_file(path)
    base = devtrace.load(path)
    by_name: Dict[str, str] = {}
    for text in hlo_texts:
        by_name.update(hlo_scopes(text))
    tr = Scoped(ops=base.ops, modules=base.modules, host=base.host,
                texts=base.texts)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(devtrace.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != devtrace.OPS_LINE:
                    continue
                tr.scopes.setdefault(plane.name, []).extend(
                    by_name.get(e.name.split(" = ", 1)[0], "")
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.program.extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(PROGRAM_PREFIXES))
    return tr


def primitive_of(scope: str, names: Iterable[str] = PRIMITIVES) -> str:
    """The innermost primitive a scope path names, else ``unscoped``."""
    names = set(names)
    found = [w for w in _WORD.findall(scope) if w in names]
    return found[-1] if found else UNSCOPED


def chunk_runs(tr: devtrace.Trace, device: str,
               w: Interval) -> List[Interval]:
    """The executions of the chunk program on ``device``, cut to ``w``."""
    return devtrace.clip([e for e in tr.modules.get(device, [])
                          if e.name.startswith(CHUNK_MODULE)], w)


def primitive_self_times(tr: Scoped, w: Optional[Interval] = None
                         ) -> Dict[str, float]:
    """Device self time (s) per primitive inside the chunk program's
    executions in ``w`` (default the trace's window), summed over
    devices: every op is renamed after the primitive of its scope and
    ``devtrace.self_times`` gives each instant to the innermost."""
    w = w or tr.window()
    acc: Dict[str, float] = {}
    for dev, events in tr.ops.items():
        scopes = tr.scopes.get(dev, [])
        if len(scopes) != len(events):
            continue                      # no scope for this device
        renamed = [Event(primitive_of(s), e.start, e.dur)
                   for e, s in zip(events, scopes)]
        for run in chunk_runs(tr, dev, w):
            inside = [e for e in renamed
                      if e.start < run[1] and e.end > run[0]]
            for k, v in devtrace.self_times(inside, run).items():
                acc[k] = acc.get(k, 0.0) + v
    return acc


def chunk_executions(tr: devtrace.Trace,
                     w: Optional[Interval] = None) -> int:
    w = w or tr.window()
    return sum(len(chunk_runs(tr, dev, w)) for dev in tr.modules)


def idle_by_span(tr: Scoped, w: Optional[Interval] = None
                 ) -> Dict[str, float]:
    """The first device's idle seconds in ``w``, each gap booked to the
    program span that overlaps it most (the shorter on a tie, so an
    inner span wins over the one around it), or ``none``."""
    w = w or tr.window()
    if not tr.ops:
        return {}
    dev = sorted(tr.ops)[0]
    busy = devtrace.union(devtrace.clip(tr.ops[dev], w))
    spans = sorted(tr.program, key=lambda e: e.start)
    out: Dict[str, float] = {}
    i, live = 0, []                  # spans started, not yet ended
    for s, t in devtrace.gaps(busy, w):     # in time order
        while i < len(spans) and spans[i].start < t:
            live.append(spans[i])
            i += 1
        live = [e for e in live if e.end > s]
        best, name = (0.0, 0.0), "none"
        for e in live:
            ov = min(t, e.end) - max(s, e.start)
            if (ov, -e.dur) > best:
                best, name = (ov, -e.dur), e.name
        out[name] = out.get(name, 0.0) + (t - s)
    return out


def cut(tr: Scoped, w: Interval) -> Scoped:
    """The part of ``tr`` that overlaps ``w``, with ``w`` as its window:
    a small trace to record for tests."""
    def keep(es):
        return [e for e in es if e.start < w[1] and e.end > w[0]]
    ops, scopes = {}, {}
    for dev, events in tr.ops.items():
        pairs = [(e, s) for e, s in zip(events, tr.scopes.get(dev, []))
                 if e.start < w[1] and e.end > w[0]]
        ops[dev] = [e for e, _ in pairs]
        scopes[dev] = [s for _, s in pairs]
    names = {e.name for es in ops.values() for e in es}
    return Scoped(ops=ops,
                  modules={d: keep(es) for d, es in tr.modules.items()},
                  host=keep([e for e in tr.host if e.name != "window"])
                  + [Event("window", w[0], w[1] - w[0])],
                  texts={k: v for k, v in tr.texts.items() if k in names},
                  scopes=scopes, program=keep(tr.program))


def write_json(tr: Scoped, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)


def read_json(path: str) -> Scoped:
    with open(path) as f:
        return Scoped.from_json(json.load(f))


def shares(times: Mapping[str, float]) -> Dict[str, float]:
    """Each entry's share (%) of their sum."""
    total = sum(times.values())
    return {k: 100.0 * v / total for k, v in times.items()} if total else {}
