"""The harness finds a configuration, a mix and a per-layer metric by the
names ``BENCHMARK.json`` gives them: a test-only cell made of new files
and new entries alone runs end to end here, on the CPU, through the
loop, the reference check and the metric readers."""
import json
import os
import subprocess
import sys
import time

import pytest

import harness
import tiny


@pytest.fixture(scope="module")
def runs():
    bench = tiny.benchmark()
    return {
        traced: harness.run_cell(tiny.CELL, 2**31 + 12345, 2.0, traced,
                                 time.perf_counter(), bench=bench,
                                 search=tiny.SEARCH, log=lambda s: None)
        for traced in (False, True)
    }


def test_untraced_run_reports_the_end_to_end_metrics(runs):
    r = runs[False]
    assert r["correct"] is True
    assert r["attempted"] == 80 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    names = {m["name"] for m in harness.load_benchmark()["end_to_end"]}
    assert set(r["metrics"]) == names
    assert r["metrics"]["query_p95_ms"]["value"] >= r["metrics"]["query_p50_ms"]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    json.dumps(r)


def test_traced_run_reads_each_layer_metric_it_finds(runs):
    r = runs[True]
    assert r["correct"] is True
    m = r["metrics"]
    # the new metric's reader was found by its name
    assert m["tiny_requests_answered"]["value"] == r["attempted"]
    assert 0 <= m["result_cache_hit_rate"]["value"] <= 100
    assert m["setup_correction_s"]["value"] > 0
    assert m["step_ms.ppr"]["value"] > 0
    # the CPU has no device plane: trace-read metrics say nothing, never 0
    for name in ("device_idle_share", "step_idle_share", "propagate_roofline"):
        assert name not in m
    assert "busy_s" not in r["device"]


def test_checks_hold_every_compared_number_beside_its_limit(runs):
    checks = runs[False]["checks"]
    assert set(checks) == {"unanswered", "ppr_gap", "bfs_wrong", "common_neighbors_wrong"}
    for c in checks.values():
        assert c["value"] <= c["limit"]


def test_command_refuses_a_cpu_and_prints_no_result():
    root = harness.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dblp-q1.ppr-uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


def test_every_benchmark_entry_has_its_files():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        cfg, module = harness.load_config(cell["config"])
        assert cfg["name"] == cell["config"]
        assert set(bench_config(bench, cell["config"])["reduced"]) == set(cfg["reduced"])
        mix = harness.load_mix(cell["traffic"])
        assert abs(sum(mix["kinds"].values()) - 1.0) < 1e-9
        assert callable(module.tables) and callable(module.incidence)
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)


def bench_config(bench, name):
    return next(c for c in bench["configs"] if c["name"] == name)
