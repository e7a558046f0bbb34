"""A run whose timed path is broken underneath comes out not correct.

Each case drives a whole run of the test-only cell past the harness's
look for a chip, with one fault planted in the system under test: an
answer altered where it is produced (each kind), the DEDUP-C correction
left out, and half of each batch's answers never delivered."""
import time

import numpy as np
import pytest

import harness
import tiny
from repro.core import algorithms, dedup
from repro.serve.tier import GraphServingTier


def _run():
    return harness.run_cell(tiny.CELL, 987654321, 2.0, False, time.perf_counter(),
                            bench=tiny.benchmark(), search=tiny.SEARCH,
                            log=lambda s: None)


def _altered(monkeypatch, name, alter):
    real = getattr(algorithms, name)
    monkeypatch.setattr(algorithms, name, lambda *a, **k: alter(real(*a, **k)))


def test_sound_run_is_correct():
    assert _run()["correct"] is True


@pytest.mark.parametrize("name, alter, check", [
    ("personalized_pagerank", lambda x: x * 1.001, "ppr_gap"),
    ("bfs_multi", lambda d: d + (d == 1), "bfs_wrong"),
    ("common_neighbors_multi", lambda c: c.at[0].add(1.0), "common_neighbors_wrong"),
])
def test_answer_altered_where_produced(monkeypatch, name, alter, check):
    _altered(monkeypatch, name, alter)
    r = _run()
    assert r["correct"] is False
    assert r["checks"][check]["value"] > r["checks"][check]["limit"]


def test_correction_left_out(monkeypatch):
    real = dedup.build_correction_streaming

    def without(graph, *a, **k):
        c = real(graph, *a, **k)
        empty = np.empty(0, dtype=np.int64)
        return dedup.StreamedCorrection(empty, empty, empty, c.accounting)

    monkeypatch.setattr(dedup, "build_correction_streaming", without)
    r = _run()
    assert r["correct"] is False
    assert r["checks"]["ppr_gap"]["value"] > r["checks"]["ppr_gap"]["limit"]


def test_half_of_each_batch_never_answered(monkeypatch):
    real = GraphServingTier.step

    def half(self, *a, **k):
        out = real(self, *a, **k)
        return out[: len(out) // 2]

    monkeypatch.setattr(GraphServingTier, "step", half)
    r = _run()
    assert r["correct"] is False
    assert r["failed"] > 0
    assert r["checks"]["unanswered"]["value"] == r["failed"]
