"""Share of the device time of the window's ``serve_ppr`` executions spent
in the DEDUP-C correction, in %: the self time of their ops whose innermost
scope is ``engine.correction`` (the segment-path epilogue) or
``engine.fused`` (the fused kernel's layer and epilogue), over the self time
of all their ops.  Scopes come from each op's ``op_name`` on the trace
(:mod:`modules`); a program without them reads nothing."""
import harness
import modules

CORRECTION = ("engine.correction", "engine.fused")


def read(run):
    if run.trace is None or run.window_ns is None:
        return None
    mods = modules.of_run(harness.RESULTS / "trace", run.window_ns)
    runs = modules.in_window(mods, *run.window_ns, "jit_serve_ppr")
    total = sum(ns for e in runs for ns in e.op_self_ns.values())
    if not total:
        return None
    correction = sum(ns for e in runs for op, ns in e.op_self_ns.items()
                     if mods.scope(e, op) in CORRECTION)
    return 100.0 * correction / total
