"""Host spans of the served path.

One helper names, times and records every host span the serving engine
and its pool own. A span is a block of host work for one chunk:

  engine.stage       the gather of queued frames into a staging set
  engine.dispatch    everything from the gathered set to the enqueued
                     chunk, made of
    fleet.build        the chunk's host arrays and offload plan
    fleet.h2d          their placement on the device
    fleet.enqueue      the jitted chunk call
    pool.feedback      host-Kalman and registration fixes that must land
                       before the next dispatch
  pool.sync          the blocking sync of the chunk's poses
  pool.host_stage    the deferred host map stage after the sync

Each span is timed on the engine's clock, its seconds go to the
``StepTimeTracker`` of its short name (``dispatch`` for
``engine.dispatch``), and it enters ``jax.profiler.TraceAnnotation`` with
the chunk's sequence number, so a profiler trace shows every span of
one chunk under one ``chunk`` id. With the profiler off the annotation
costs a few microseconds.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Iterator

import jax

from repro.launch.watchdog import StepTimeTracker

SPANS = ("engine.stage", "engine.dispatch", "fleet.build", "fleet.h2d",
         "fleet.enqueue", "pool.feedback", "pool.sync", "pool.host_stage")


def short(name: str) -> str:
    """A span's tracker key: its name after the layer's prefix."""
    return name.rsplit(".", 1)[-1]


class Spans:
    """``spans(name, chunk)`` is a context manager for span ``name`` of
    chunk number ``chunk``; ``trackers`` holds one ``StepTimeTracker`` per
    span, keyed by ``short(name)``."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.trackers: Dict[str, StepTimeTracker] = {
            short(n): StepTimeTracker() for n in SPANS}

    @contextlib.contextmanager
    def __call__(self, name: str, chunk: int) -> Iterator[None]:
        tracker = self.trackers[short(name)]
        with jax.profiler.TraceAnnotation(name, chunk=chunk):
            t0 = self.clock()
            yield
            tracker.add(self.clock() - t0)
