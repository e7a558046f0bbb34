"""``modules.py`` and the readers built on it, on two small traces recorded
on a TPU v5e: ``trace_small`` (``record_trace.py``, a program whose
executables were all named ``raw``) and ``trace_program``
(``record_program_trace.py``, executables named per kind, the program's
recorder on)."""
import collections
import shutil
from pathlib import Path

import pytest

import harness
import modules
import trace

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "trace_small.xplane.pb"
NEW = DATA / "trace_program.xplane.pb"
READERS = ("device_ms.ppr", "ppr_correction_share")


def _run(path):
    tr = trace.read(str(path))
    (_, lo, hi), = tr.spans_named("bench.window")
    return harness.Run(shape={}, setup={}, window=None, schedule=None, cache={},
                       tier={}, trace=tr, window_ns=(lo, hi), peak=None)


@pytest.fixture
def results(tmp_path, monkeypatch):
    """``harness.RESULTS`` holding one recorded trace, as a traced run
    leaves it."""
    monkeypatch.setattr(harness, "RESULTS", tmp_path)

    def place(path):
        d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(path, d / "host.xplane.pb")
        return _run(path)

    return place


@pytest.mark.parametrize("path", [OLD, NEW], ids=["old", "program"])
def test_module_executions_nest_every_op(path):
    mods = modules.read(str(path))
    tr = trace.read(str(path))
    assert mods.executions
    ex = mods.executions
    assert all(a.end_ns <= b.start_ns for a, b in zip(ex, ex[1:]))
    # every op falls inside one execution, so the per-module self times
    # add up to trace.py's, op by op
    per_op = collections.Counter()
    for e in ex:
        per_op.update(e.op_self_ns)
    assert per_op.keys() == tr.op_time.keys()
    for op, ns in tr.op_time.items():
        assert per_op[op] == pytest.approx(ns), op


def test_reading_modules_leaves_trace_readings_as_they_were():
    run = _run(OLD)
    lo, hi = run.window_ns
    idle = harness.load_reader("device_idle_share").read(run)
    gaps = run.trace.idle_by_span(lo, hi, lambda s: s[0])
    modules.read(str(OLD))
    again = _run(OLD)
    assert harness.load_reader("device_idle_share").read(again) == idle
    assert again.trace.op_time == run.trace.op_time
    assert again.trace.spans == run.trace.spans
    assert again.trace.idle_by_span(lo, hi, lambda s: s[0]) == gaps


@pytest.mark.parametrize("metric", READERS)
def test_new_readers_read_nothing_without_named_executables(metric, results):
    run = results(OLD)
    names = {e.module for e in modules.of_run(harness.RESULTS / "trace",
                                              run.window_ns).executions}
    assert "jit_raw" in names and "jit_serve_ppr" not in names
    assert harness.load_reader(metric).read(run) is None


@pytest.mark.parametrize("metric", READERS)
def test_new_readers_read_nothing_without_a_trace(metric):
    run = harness.Run({}, {}, None, None, {}, {}, None, None, None)
    assert harness.load_reader(metric).read(run) is None


def test_device_ms_ppr_reads_serve_ppr_executions(results):
    run = results(NEW)
    lo, hi = run.window_ns
    ms = harness.load_reader("device_ms.ppr").read(run)
    runs = modules.in_window(modules.read(str(NEW)), lo, hi, "jit_serve_ppr")
    assert runs and ms > 0
    # a batch step waits for its answers on the host, so it holds its execution
    steps = sorted((s[2] - s[1]) / 1e6 for s in run.trace.spans_named("bench.step"))
    assert ms <= steps[-1]


def test_ppr_correction_share_is_a_share_of_scoped_time(results):
    run = results(NEW)
    share = harness.load_reader("ppr_correction_share").read(run)
    assert 0 < share < 100


def test_scopes_cover_nearly_all_serve_ppr_time():
    """The trace's own ``op_name`` of each op names a program scope for at
    least nine tenths of the ``serve_ppr`` op self time."""
    mods = modules.read(str(NEW))
    runs = [e for e in mods.executions if e.module == "jit_serve_ppr"]
    assert runs
    total = sum(ns for e in runs for ns in e.op_self_ns.values())
    mapped = sum(ns for e in runs for op, ns in e.op_self_ns.items() if mods.scope(e, op))
    assert mapped >= 0.9 * total
    scopes = {mods.scope(e, op) for e in runs for op in e.op_self_ns}
    assert {"engine.layer", "engine.correction", "ppr.update"} <= scopes


@pytest.mark.parametrize("op_name, scope", [
    ("jit(serve_ppr)/while/body/closed_call/engine.correction/mul", "engine.correction"),
    ("jit(serve_ppr)/engine.layer/jit(_take)/gather", "engine.layer"),
    ("jit(serve_ppr)/while/body/ppr.update/engine.layer/add", "engine.layer"),
    ("jit(serve_ppr)/jit(personalized_pagerank)/while", None),
    ("", None),
])
def test_scope_of_takes_the_innermost_module_scope(op_name, scope):
    assert modules.scope_of(op_name) == scope


@pytest.mark.parametrize("metric", READERS)
def test_new_readers_refuse_a_trace_of_another_run(metric, results):
    """A stale trace under ``results/trace`` that is not the run's own
    (another window) is an error, not a reading."""
    run = results(NEW)
    lo, hi = run.window_ns
    run.window_ns = (lo + 1.0, hi)
    with pytest.raises(ValueError, match="does not hold the run's window"):
        harness.load_reader(metric).read(run)


def test_program_spans_nest_inside_the_bench_steps():
    spans = modules.program_spans(str(NEW))
    tr = trace.read(str(NEW))
    (_, lo, hi), = tr.spans_named("bench.window")
    steps = tr.spans_named("bench.step")
    tier_steps = [s for s in spans if s[0] == "tier.step" and lo <= s[1] <= hi]
    assert len(tier_steps) == len(steps) > 0
    for outer, inner in zip(steps, tier_steps):
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
    children = [s for s in spans if s[0] in ("tier.dispatch", "tier.fetch", "tier.record")
                and lo <= s[1] <= hi]
    assert len(children) == 3 * len(tier_steps)
    for c in children:
        assert any(t[1] <= c[1] and c[2] <= t[2] for t in tier_steps)
