"""Roofline share of the interior layer steps of PPR, in %: the least time
the chip needs for the window's PPR batches' interior work (bytes from the
graph's shape numbers, :func:`interior_bytes`, over the device's HBM
bandwidth) over the self time of the window's ``serve_ppr`` ops whose
innermost scope is ``engine.interior``.  A program whose ops carry no such
scope reads nothing."""
import harness
import modules

INTERIOR = "engine.interior"


def interior_bytes(shape: dict, width: int, iters: int) -> float:
    """Bytes the interior steps of one PPR batch of ``width`` columns must
    move: for each step from one virtual layer to the next, the rows of
    both layers in float32 and both int32 indices of each of its condensed
    edges, once per power iteration at ``width`` columns and once at one
    column for the batch's out-degrees (whose steps carry the same
    scope)."""
    rows = edges = 0
    for c in shape["chains"]:
        layers = c["virtual_layers"]
        for i in range(1, len(layers)):
            rows += layers[i - 1] + layers[i]
            edges += c["edges"][i]
    return float(iters * (4 * width * rows + 8 * edges) + 4 * rows + 8 * edges)


def read(run):
    if run.trace is None or run.window_ns is None or run.peak is None:
        return None
    mods = modules.of_run(harness.RESULTS / "trace", run.window_ns)
    runs = modules.in_window(mods, *run.window_ns, "jit_serve_ppr")
    busy_ns = sum(ns for e in runs for op, ns in e.op_self_ns.items()
                  if mods.scope(e, op) == INTERIOR)
    steps = [s for s in run.window.steps if s.kind == "ppr"]
    if not busy_ns or not steps:
        return None
    least_s = sum(interior_bytes(run.shape, s.width, run.tier["ppr_iters"])
                  for s in steps) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (busy_ns / 1e9)
