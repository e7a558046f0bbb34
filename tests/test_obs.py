"""The program's recorder (``repro.obs``): off it records nothing and opens
no profiler annotation; on, spans nest and add up, counters and samples
keep what they are given, and the device scopes reach the compiled HLO."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import copurchase_graph, random_membership_graph

from repro import obs
from repro.core import dedup, engine
from repro.serve import GraphServingTier, ServeRequest


@pytest.fixture(autouse=True)
def recorder():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``, keeping every call."""

    def __init__(self):
        self.opened = []

    def __call__(self, name, **args):
        self.opened.append((name, args))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    fake = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake)
    return fake


def _graph(seed=0):
    return random_membership_graph(40, 14, 4, np.random.default_rng(seed))


def test_off_records_nothing_and_opens_no_annotation(annotations):
    assert not obs.enabled()
    assert obs.span("tier.step", kind="ppr") is obs.span("engine.pack")
    with obs.span("tier.step", kind="ppr"):
        obs.count("tier.kernel_layer_calls", 3)
        obs.sample("tier.queue_wait_s", 0.5)
    g = _graph()
    dedup.build_correction_streaming(g, chunk_rows=4)
    tier = GraphServingTier(max_batch=4)
    tier.add_tenant("A", g, packed=True)
    tier.serve([ServeRequest(i, "A", "ppr", i) for i in range(3)])
    assert annotations.opened == []
    assert obs.snapshot() == {"spans": {}, "counts": {}, "samples": {}}
    assert tier._admitted == {}


def test_nested_spans_add_up(annotations):
    obs.enable()
    with obs.span("tier.step", kind="ppr", width=8):
        for _ in range(2):
            with obs.span("tier.fetch"):
                time.sleep(0.01)
        time.sleep(0.005)
    spans = obs.snapshot()["spans"]
    assert spans["tier.fetch"]["count"] == 2
    assert spans["tier.step"]["count"] == 1
    assert spans["tier.fetch"]["seconds"] >= 0.02
    assert spans["tier.step"]["seconds"] >= spans["tier.fetch"]["seconds"] + 0.005
    assert annotations.opened == [
        ("tier.step", {"kind": "ppr", "width": 8}),
        ("tier.fetch", {}),
        ("tier.fetch", {}),
    ]


def test_a_span_counts_when_its_body_raises(annotations):
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("engine.pack"):
            raise ValueError("duplicate edges")
    assert obs.snapshot()["spans"]["engine.pack"]["count"] == 1


def test_snapshot_reset_and_bounded_samples(monkeypatch):
    monkeypatch.setattr(obs, "SAMPLE_LIMIT", 3)
    obs.enable()
    obs.count("tier.kernel_layer_calls", 2)
    obs.count("tier.kernel_layer_calls")
    for v in range(5):
        obs.sample("tier.queue_wait_s", v)
    snap = obs.snapshot()
    assert snap["counts"] == {"tier.kernel_layer_calls": 3}
    assert snap["samples"] == {"tier.queue_wait_s": [2.0, 3.0, 4.0]}
    snap["counts"]["tier.kernel_layer_calls"] = 99   # a copy, not the state
    assert obs.snapshot()["counts"]["tier.kernel_layer_calls"] == 3
    obs.reset()
    assert obs.enabled()
    assert obs.snapshot() == {"spans": {}, "counts": {}, "samples": {}}
    obs.disable()
    obs.count("tier.kernel_layer_calls")
    assert obs.snapshot()["counts"] == {}


def test_correction_build_spans_nest_inside_the_build():
    g = _graph(1)
    obs.enable()
    corr = dedup.build_correction_streaming(g, budget_triples=64)
    spans = obs.snapshot()["spans"]
    assert spans["dedup.correction"]["count"] == 1
    assert spans["condensed.expand"]["count"] == corr.accounting.n_chunks > 1
    assert spans["condensed.fold"]["count"] >= 2
    assert spans["dedup.finish"]["count"] == 1
    inner = sum(spans[k]["seconds"] for k in ("condensed.expand", "condensed.fold", "dedup.finish"))
    assert inner <= spans["dedup.correction"]["seconds"]


def test_packed_upload_splits_packing_from_transfer():
    g = _graph(2)
    corr = dedup.build_correction(g)
    obs.enable()
    t = time.perf_counter()
    dev = engine.to_device_packed(g, correction=corr, backend="pallas")
    wall = time.perf_counter() - t
    spans = obs.snapshot()["spans"]
    # both directions of every layer; the correction planes of both
    # directions, then each direction's fused stream
    n_layers = sum(len(c.edges) for c in g.chains)
    assert spans["engine.pack"]["count"] == n_layers + 3
    assert spans["engine.upload"]["count"] == 1 + 2 * n_layers + 2
    assert dev.fused_fwd is not None
    assert spans["engine.pack"]["seconds"] + spans["engine.upload"]["seconds"] <= wall


def _ppr_batch_hlo(g):
    """HLO text of one packed PPR batch over ``g``, as lowered (with its
    debug info) and as compiled for the CPU."""
    tier = GraphServingTier(max_batch=8)
    tier.add_tenant("A", g, packed=True)
    tier._ensure_resident(tier.tenants["A"])
    graph = engine.with_graph_version(tier.tenants["A"].device, 0)
    nodes = jnp.arange(8, dtype=jnp.int32)
    lowered = tier._build_executable("ppr").fn.lower(graph, nodes)
    return {"lowered": lowered.as_text(debug_info=True),
            "compiled": lowered.compile().as_text()}


def _carries(text, stage, scope):
    """``scope`` is a path element of some instruction's location, and
    after compiling of its ``op_name``, which the device trace carries as
    the op's ``tf_op``."""
    key = {"lowered": "loc", "compiled": "op_name="}[stage]
    return re.search(re.escape(key) + r'\(?"(?:[^"]*/)?' + re.escape(scope) + "/", text)


@pytest.fixture(scope="module")
def ppr_hlo():
    return _ppr_batch_hlo(_graph(3))


@pytest.fixture(scope="module")
def copurchase_hlo():
    """A chain of three virtual layers: orders, parts, orders."""
    return _ppr_batch_hlo(copurchase_graph(42, 130, 26, np.random.default_rng(3)))


@pytest.mark.parametrize("stage", ["lowered", "compiled"])
@pytest.mark.parametrize("scope", ["engine.layer", "engine.correction", "ppr.update"])
def test_packed_ppr_batch_carries_the_device_scopes(ppr_hlo, stage, scope):
    assert _carries(ppr_hlo[stage], stage, scope)


@pytest.mark.parametrize("stage", ["lowered", "compiled"])
@pytest.mark.parametrize("chain", ["three_virtual_layers", "one_virtual_layer"])
def test_interior_scope_marks_only_steps_between_virtual_layers(
        ppr_hlo, copurchase_hlo, stage, chain):
    """``engine.interior`` runs inside ``engine.layer`` on a step from one
    virtual layer to another; a chain of one virtual layer has none."""
    text = {"three_virtual_layers": copurchase_hlo, "one_virtual_layer": ppr_hlo}[chain]
    assert bool(_carries(text[stage], stage, "engine.layer/engine.interior")) == (
        chain == "three_virtual_layers")
    assert bool(_carries(text[stage], stage, "engine.interior")) == (
        chain == "three_virtual_layers")


def test_fused_epilogue_carries_its_scope():
    g = _graph(4)
    dev = engine.to_device_packed(g, correction=dedup.build_correction(g),
                                  backend="pallas")
    assert dev.fused_fwd is not None
    x = jnp.ones((g.n_real, 8), dtype=jnp.float32)
    text = jax.jit(lambda d, x: engine.propagate(d, x)).lower(dev, x).as_text(
        debug_info=True)
    assert re.search(r'loc\("(?:[^"]*/)?engine\.fused/', text)
