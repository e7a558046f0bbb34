"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

    python bench/trace.py results/trace/<cell>/...xplane.pb   # look by hand

A device plane is a plane named ``/device:<kind>:<n>``; its operations
are the events on its ``XLA Ops`` line.  Host spans are the benchmark's
own ``jax.profiler.TraceAnnotation`` events (names starting ``bench.``)
on the host plane.  Both are read from ``jax.profiler.ProfileData``,
which puts them on one clock in nanoseconds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import sys

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10


def newest_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def _profile(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of ``(start, end)`` rows, as disjoint sorted rows."""
    if intervals.size == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    end = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > end[:-1]])
    starts = iv[new, 0]
    ends = end[np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])]
    return np.stack([starts, ends], axis=1)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint rows of ``merged`` cover."""
    if merged.size == 0 or hi <= lo:
        return 0.0
    a = np.clip(merged[:, 0], lo, hi)
    b = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(b - a))


@dataclasses.dataclass
class Trace:
    devices: dict     # plane name -> merged (start, end) rows, ns
    op_time: dict     # op name -> device ns, summed over devices
    spans: list       # (name, start_ns, end_ns) of the bench's host spans

    def busy_ns(self, lo: float, hi: float) -> float:
        """Device-busy time inside ``[lo, hi]``, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(covered(m, lo, hi) for m in self.devices.values()) / len(
            self.devices
        )

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]

    def idle_by_span(self, lo: float, hi: float, label) -> dict:
        """Idle device time in ``[lo, hi]`` (of the first device), split by
        the host span open over it; ``label`` names each span."""
        if not self.devices:
            return {}
        merged = next(iter(self.devices.values()))
        inside = merged[(merged[:, 1] > lo) & (merged[:, 0] < hi)]
        edges = np.concatenate([[lo], inside.ravel(), [hi]]).clip(lo, hi)
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        out = collections.Counter()
        for span in self.spans:
            name = label(span)
            if name is None:
                continue
            a = np.clip(gaps[:, 0], span[1], span[2])
            b = np.clip(gaps[:, 1], span[1], span[2])
            out[name] += float(np.sum(b - a))
        return dict(out)


def op_name(event_name: str) -> str:
    """``%fusion.91`` of ``%fusion.91 = f32[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0]


def self_times(events) -> dict:
    """Device ns by op name, each op less the ops nested in it on the same
    line (a ``while`` holds its body's ops)."""
    out = collections.Counter()
    stack = []   # (end_ns, name) of the ops open at the current start
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= end - start
        out[name] += end - start
        stack.append((end, name))
    return dict(out)


def read(path: str) -> Trace:
    devices, op_time, spans = {}, collections.Counter(), []
    for plane in _profile(path).planes:
        if plane.name.startswith("/device:"):
            rows = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = [(ev.start_ns, ev.end_ns, op_name(ev.name)) for ev in line.events]
                rows += [e[:2] for e in events]
                op_time.update(self_times(events))
            if rows:
                devices[plane.name] = merge(np.asarray(rows, dtype=np.float64))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    spans.sort(key=lambda s: s[1])
    return Trace(devices=devices, op_time=dict(op_time), spans=spans)


def describe(path: str) -> dict:
    """Every plane and line with its event count and a few event names."""
    out = []
    for plane in _profile(path).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({
                "line": line.name,
                "events": len(events),
                "first": [(e.name, e.start_ns, e.duration_ns) for e in events[:3]],
            })
        out.append({"plane": plane.name, "lines": lines})
    return {"path": path, "planes": out}


if __name__ == "__main__":
    print(json.dumps(describe(sys.argv[1]), indent=1))
