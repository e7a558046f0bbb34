"""The readers of the interior layer steps, ``ppr_interior_share`` and
``interior_roofline``: nothing on a trace of a program with one virtual
layer (``trace_program``, recorded by ``record_program_trace.py``), a
number on a two-second chip trace of ``tpch-copurchase.ppr-uniform``
(``trace_tpch_copurchase``, recorded by ``record_cell_trace.py`` with the
run's shape numbers and batch widths beside it), and the interior work
counted by hand."""
import json
import shutil
from pathlib import Path

import pytest

import harness
import modules
import trace

DATA = Path(__file__).resolve().parent / "data"
ONE_LAYER = DATA / "trace_program.xplane.pb"
INTERIOR = DATA / "trace_tpch_copurchase.xplane.pb"
READERS = ("ppr_interior_share", "interior_roofline")
PEAK = {"hbm_bytes_per_s": 819e9}
TPCH_SHAPE = {"n_real": 24_000, "direct_edges": 0, "correction_triples": 1_734_932,
              "chains": [{"virtual_layers": [240_000, 32_000, 240_000],
                          "edges": [240_000, 960_633, 960_633, 240_000]}]}


def _run(path):
    """The run that recorded ``path``, as far as its side file tells."""
    tr = trace.read(str(path))
    (_, lo, hi), = tr.spans_named("bench.window")
    side = path.with_name(path.name.replace(".xplane.pb", ".run.json"))
    shape, tier, steps = {"chains": []}, {"ppr_iters": 20}, []
    if side.exists():
        meta = json.loads(side.read_text())
        shape, tier = meta["shape"], meta["tier"]
        steps = [harness.Step(kind, width, width, 0.0, 0.0) for kind, width in meta["steps"]]
    window = harness.Window(None, None, {}, steps, 0.0, 0.0)
    return harness.Run(shape=shape, setup={}, window=window, schedule=None, cache={},
                       tier=tier, trace=tr, window_ns=(lo, hi), peak=PEAK)


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


def _interior_bytes():
    return harness.load_reader("interior_roofline").interior_bytes


def test_interior_bytes_by_hand():
    count = _interior_bytes()
    one = {"chains": [{"virtual_layers": [10], "edges": [30, 30]}]}
    assert count(one, 32, 20) == 0.0
    # real -> a -> b -> real: the one interior step a -> b
    two = {"chains": [{"virtual_layers": [7, 5], "edges": [9, 11, 13]}]}
    # three iterations at width 8, and the out-degrees at width 1
    assert count(two, 8, 3) == 3 * (4 * 8 * (7 + 5) + 8 * 11) + 4 * (7 + 5) + 8 * 11
    # two chains add up
    assert count({"chains": two["chains"] * 2}, 8, 3) == 2 * count(two, 8, 3)


def test_interior_bytes_of_the_tpch_chain():
    """Orders -> parts and parts -> orders: 544,000 rows and 1,921,266
    edges, 85.0 MB an iteration at width 32 and 17.5 MB for the
    out-degrees."""
    per_iter = 4 * 32 * (240_000 + 32_000 + 32_000 + 240_000) + 8 * (960_633 + 960_633)
    degrees = 4 * (240_000 + 32_000 + 32_000 + 240_000) + 8 * (960_633 + 960_633)
    assert (per_iter, degrees) == (85_002_128, 17_546_128)
    assert _interior_bytes()(TPCH_SHAPE, 32, 20) == 20 * per_iter + degrees


@pytest.mark.parametrize("metric", READERS)
def test_read_nothing_on_a_program_with_one_virtual_layer(metric, results):
    run = results(ONE_LAYER)
    mods = modules.of_run(harness.RESULTS / "trace", run.window_ns)
    assert any(e.module == "jit_serve_ppr" for e in mods.executions)
    run.shape, run.window.steps = TPCH_SHAPE, [harness.Step("ppr", 8, 8, 0.0, 0.0)]
    assert harness.load_reader(metric).read(run) is None


@pytest.mark.parametrize("metric", READERS)
def test_read_nothing_without_a_trace(metric):
    run = harness.Run({}, {}, None, None, {}, {}, None, None, None)
    assert harness.load_reader(metric).read(run) is None


def test_ppr_interior_share_reads_the_interior_scope(results):
    run = results(INTERIOR)
    share = harness.load_reader("ppr_interior_share").read(run)
    assert 0 < share < 100
    mods = modules.read(str(INTERIOR))
    runs = modules.in_window(mods, *run.window_ns, "jit_serve_ppr")
    scopes = {mods.scope(e, op) for e in runs for op in e.op_self_ns}
    assert {"engine.interior", "engine.layer", "engine.correction"} <= scopes


def test_interior_roofline_reads_a_share_of_the_interior_time(results):
    run = results(INTERIOR)
    assert run.shape["chains"] == TPCH_SHAPE["chains"]
    assert [s.kind for s in run.window.steps] and {s.kind for s in run.window.steps} == {"ppr"}
    share = harness.load_reader("interior_roofline").read(run)
    assert 0 < share < 100
    # the same count over the interior ops' self time, by hand
    mods = modules.read(str(INTERIOR))
    runs = modules.in_window(mods, *run.window_ns, "jit_serve_ppr")
    busy = sum(ns for e in runs for op, ns in e.op_self_ns.items()
               if mods.scope(e, op) == "engine.interior") / 1e9
    least = sum(_interior_bytes()(run.shape, s.width, 20) for s in run.window.steps) / 819e9
    assert share == pytest.approx(100 * least / busy)


@pytest.mark.parametrize("metric", READERS)
def test_readers_refuse_a_trace_of_another_run(metric, results):
    run = results(INTERIOR)
    lo, hi = run.window_ns
    run.window_ns = (lo + 1.0, hi)
    with pytest.raises(ValueError, match="does not hold the run's window"):
        harness.load_reader(metric).read(run)
