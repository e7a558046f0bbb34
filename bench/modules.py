"""The device trace by XLA module: which program each device op ran in,
and the scope the program gave it.

A device plane's ``XLA Modules`` line holds one event per execution of a
compiled program, named ``<module>(<program id>)``; the serving tier names
its module after the kind it serves (``jit_serve_ppr``).  Each event on the
``XLA Ops`` line falls inside one of them.  The op's metadata on the plane
carries ``tf_op``, the ``op_name`` path of the HLO instruction, in which
``jax.named_scope`` puts the program's scopes
(``jit(serve_ppr)/while/body/engine.correction/mul``).
``jax.profiler.ProfileData`` does not expose event metadata, so the plane's
metadata is read with a minimal copy of the ``XSpace`` message (the fields
used here, numbered as in ``xplane.proto``).

This module leaves :mod:`trace` and every number read through it as they are.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import re

import numpy as np

import trace

MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
# a module-prefixed name, as the program names its spans and scopes
_SCOPE = re.compile(r"[A-Za-z_]\w*\.\w+")
PROGRAM_PREFIXES = ("tier.", "engine.", "condensed.", "dedup.")


@dataclasses.dataclass
class Execution:
    module: str       # ``jit_serve_ppr``: the event's name less its program id
    program_id: str
    start_ns: float
    end_ns: float
    op_self_ns: dict  # op name -> self time inside this execution


@dataclasses.dataclass
class Modules:
    executions: list   # Execution, in start order, first device plane
    op_names: dict     # (program id, op name) -> op_name path
    windows: list      # (start, end) of each ``bench.window`` host span

    def scope(self, e: Execution, op: str):
        return scope_of(self.op_names.get((e.program_id, op), ""))


def scope_of(op_name: str):
    """The innermost module-prefixed scope of an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if _SCOPE.fullmatch(part):
            return part
    return None


def split_module(event_name: str) -> tuple:
    """``("jit_serve_ppr", "1452")`` of ``jit_serve_ppr(1452)``."""
    name, _, rest = event_name.partition("(")
    return name, rest.rstrip(")")


def _xspace():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_min.proto", package="bench_xplane_min", syntax="proto3")

    def message(name, fields, parent=None):
        m = (parent.nested_type if parent is not None else fp.message_type).add(name=name)
        for number, field, kind, type_name in fields:
            label = F.LABEL_REPEATED if type_name and not type_name.endswith("!") else F.LABEL_OPTIONAL
            f = m.field.add(name=field, number=number, type=kind, label=label)
            if type_name:
                f.type_name = "." + fp.package + "." + type_name.rstrip("!")
        return m

    message("XStat", [(1, "metadata_id", F.TYPE_INT64, None),
                      (3, "uint64_value", F.TYPE_UINT64, None),
                      (4, "int64_value", F.TYPE_INT64, None),
                      (5, "str_value", F.TYPE_STRING, None)])
    message("XEventMetadata", [(2, "name", F.TYPE_STRING, None),
                               (5, "stats", F.TYPE_MESSAGE, "XStat")])
    message("XStatMetadata", [(2, "name", F.TYPE_STRING, None)])
    plane = message("XPlane", [(2, "name", F.TYPE_STRING, None),
                               (4, "event_metadata", F.TYPE_MESSAGE, "XPlane.EventMeta"),
                               (5, "stat_metadata", F.TYPE_MESSAGE, "XPlane.StatMeta")])
    for entry, value in (("EventMeta", "XEventMetadata!"), ("StatMeta", "XStatMetadata!")):
        m = message(entry, [(1, "key", F.TYPE_INT64, None),
                            (2, "value", F.TYPE_MESSAGE, value)], parent=plane)
        m.options.map_entry = True
    message("XSpace", [(1, "planes", F.TYPE_MESSAGE, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(fp.package + ".XSpace"))


def op_names(path: str) -> dict:
    """``(program id, op name) -> op_name path`` of every device op."""
    with open(path, "rb") as f:
        space = _xspace().FromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            stats = {stat.get(s.metadata_id): s for s in md.stats}
            tf_op, program = stats.get("tf_op"), stats.get("program_id")
            if tf_op is not None and program is not None:
                # the program id is an integer stat; the module's event names it in decimal
                pid = str(program.uint64_value or program.int64_value or program.str_value)
                out[(pid, trace.op_name(md.name))] = tf_op.str_value.rstrip(":")
    return out


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float) -> Modules:
    executions, windows = [], []
    for plane in trace._profile(path).planes:
        if plane.name.startswith("/host:"):
            windows += [(ev.start_ns, ev.end_ns) for line in plane.lines
                        for ev in line.events if ev.name == WINDOW_SPAN]
        if executions or not plane.name.startswith("/device:"):
            continue   # one chip: the first device plane with modules
        mods, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
            elif line.name == trace.OPS_LINE:
                ops = [(ev.start_ns, ev.end_ns, trace.op_name(ev.name)) for ev in line.events]
        if not mods:
            continue
        mods.sort()
        starts = np.asarray([m[0] for m in mods])
        nested = collections.defaultdict(list)
        for op in ops:
            # by its start: an op's end may pass its module's by a rounded ns
            i = int(np.searchsorted(starts, op[0], side="right")) - 1
            if i >= 0 and op[0] < mods[i][1]:
                nested[i].append(op)
        for i, (start, end, name) in enumerate(mods):
            module, program_id = split_module(name)
            executions.append(Execution(module, program_id, start, end,
                                        trace.self_times(nested[i])))
    return Modules(executions, op_names(path), windows)


def read(path: str) -> Modules:
    return _read(path, os.path.getmtime(path))


def of_run(results_trace_dir, window_ns) -> Modules:
    """The trace of the run whose ``bench.window`` span is ``window_ns``:
    the newest under ``results/trace``, which a traced run writes and reads
    last.  Any other file (a stale trace, another cell's) raises."""
    path = trace.newest_xplane(str(results_trace_dir))
    mods = read(path)
    if tuple(window_ns) not in mods.windows:
        raise ValueError(f"{path} does not hold the run's window {tuple(window_ns)}")
    return mods


def in_window(mods: Modules, lo: float, hi: float, module: str) -> list:
    return [e for e in mods.executions
            if e.module == module and lo <= e.start_ns and e.end_ns <= hi]


def program_spans(path: str) -> list:
    """``(name, start_ns, end_ns)`` of the program's own host spans."""
    out = []
    for plane in trace._profile(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#", 1)[0]
                    if name.startswith(PROGRAM_PREFIXES):
                        out.append((name, ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda s: s[1])
