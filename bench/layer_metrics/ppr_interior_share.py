"""Share of the device time of the window's ``serve_ppr`` executions spent
in the interior layer steps of the condensed chains, in %: the self time
of their ops whose innermost scope is ``engine.interior`` (a step from one
virtual layer to another), over the self time of all their ops.  Scopes
come from each op's ``op_name`` on the trace (:mod:`modules`); a program
whose ops carry no such scope (a chain with one virtual layer, or a
program without the scope) reads nothing."""
import harness
import modules

INTERIOR = "engine.interior"


def read(run):
    if run.trace is None or run.window_ns is None:
        return None
    mods = modules.of_run(harness.RESULTS / "trace", run.window_ns)
    runs = modules.in_window(mods, *run.window_ns, "jit_serve_ppr")
    total = sum(ns for e in runs for ns in e.op_self_ns.values())
    interior = sum(ns for e in runs for op, ns in e.op_self_ns.items()
                   if mods.scope(e, op) == INTERIOR)
    if not interior:
        return None
    return 100.0 * interior / total
