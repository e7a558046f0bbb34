"""Spans, counters and samples the program records about its own work.

The recorder is off by default.  Off, :func:`span` is one flag check that
returns a shared null context, and :func:`count` and :func:`sample` return
at once: no clock is read and nothing is allocated.  On
(:func:`enable`), a span opens a ``jax.profiler.TraceAnnotation`` of its
name, so it lands on the device trace's clock when a profiler trace is
being collected, and adds its duration to per-name totals.

Names are prefixed by the module that owns the work (``tier.``,
``engine.``, ``condensed.``, ``dedup.``).  Device work is named with
``jax.named_scope`` under the same rule (``engine.layer``,
``engine.correction``, ``engine.fused``, ``ppr.update``): the scopes are
in each HLO instruction's ``op_name``, which the device trace carries.

``snapshot()`` returns plain data::

    {"spans": {name: {"count": n, "seconds": s}},
     "counts": {name: n},
     "samples": {name: [value, ...]}}      # the last SAMPLE_LIMIT per name
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict

__all__ = [
    "SAMPLE_LIMIT",
    "count",
    "disable",
    "enable",
    "enabled",
    "reset",
    "sample",
    "snapshot",
    "span",
]

SAMPLE_LIMIT = 65536

_NULL = contextlib.nullcontext()


class Recorder:
    def __init__(self) -> None:
        self.on = False
        self.reset()

    def reset(self) -> None:
        self.spans: Dict[str, list] = {}       # name -> [count, seconds]
        self.counts: Dict[str, int] = collections.Counter()
        self.samples: Dict[str, collections.deque] = {}

    @contextlib.contextmanager
    def _span(self, name: str, args: dict):
        import jax

        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **args):
                yield
        finally:
            total = self.spans.setdefault(name, [0, 0.0])
            total[0] += 1
            total[1] += time.perf_counter() - t

    def sample(self, name: str, value: float) -> None:
        q = self.samples.get(name)
        if q is None:
            q = self.samples[name] = collections.deque(maxlen=SAMPLE_LIMIT)
        q.append(float(value))

    def snapshot(self) -> dict:
        return {
            "spans": {k: {"count": c, "seconds": s} for k, (c, s) in self.spans.items()},
            "counts": dict(self.counts),
            "samples": {k: list(q) for k, q in self.samples.items()},
        }


_recorder = Recorder()


def enable() -> None:
    _recorder.on = True


def disable() -> None:
    _recorder.on = False


def enabled() -> bool:
    return _recorder.on


def reset() -> None:
    """Drop everything recorded; on or off stays as it was."""
    _recorder.reset()


def snapshot() -> dict:
    return _recorder.snapshot()


def span(name: str, **args):
    """A context manager timing ``name``; ``args`` go to the trace event."""
    if not _recorder.on:
        return _NULL
    return _recorder._span(name, args)


def count(name: str, n: int = 1) -> None:
    if _recorder.on:
        _recorder.counts[name] += int(n)


def sample(name: str, value: float) -> None:
    """Keep ``value`` among the last :data:`SAMPLE_LIMIT` of ``name``."""
    if _recorder.on:
        _recorder.sample(name, value)

