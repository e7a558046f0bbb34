"""``trace.py`` on a small trace recorded on a TPU v5e
(``record_trace.py``: the test-only cell for two seconds), and its
interval arithmetic on made-up intervals."""
from pathlib import Path

import numpy as np
import pytest

import trace

DATA = Path(__file__).resolve().parent / "data" / "trace_small.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.read(str(DATA))


def test_merge_and_covered_on_known_intervals():
    iv = np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [7.0, 8.0], [10.0, 11.0]])
    m = trace.merge(iv)
    assert m.tolist() == [[0.0, 3.0], [5.0, 8.0], [10.0, 11.0]]
    assert trace.covered(m, 2.0, 10.5) == 1.0 + 3.0 + 0.5
    assert trace.covered(trace.merge(np.empty((0, 2))), 0.0, 1.0) == 0.0


def test_self_times_take_nested_ops_out_of_their_parent():
    events = [(0, 10, "%while.1"), (1, 3, "%fusion.2"), (4, 9, "%call.3"),
              (5, 6, "%fusion.2"), (12, 14, "%copy.4")]
    assert trace.self_times(events) == {
        "%while.1": 10 - 2 - 5, "%fusion.2": 3, "%call.3": 5 - 1, "%copy.4": 2,
    }
    assert trace.op_name("%fusion.91 = f32[8]{0} fusion(s32[8] %p)") == "%fusion.91"


def test_the_recorded_trace_has_a_device_and_the_bench_spans(tr):
    assert len(tr.devices) == 1
    assert next(iter(tr.devices)).startswith("/device:TPU:")
    names = {s[0] for s in tr.spans}
    assert {"bench.window", "bench.step", "bench.admit"} <= names
    assert sum(tr.op_time.values()) > 0


def test_device_busy_union_matches_a_plain_sweep(tr):
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(str(DATA)).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    rows += [(e.start_ns, e.end_ns) for e in line.events]
    rows = np.asarray(rows, dtype=np.float64)
    points = np.unique(rows)
    mid = (points[:-1] + points[1:]) / 2
    inside = ((rows[None, :, 0] <= mid[:, None]) & (mid[:, None] <= rows[None, :, 1])).any(1)
    covered = float(np.sum(np.diff(points)[inside]))
    merged = next(iter(tr.devices.values()))
    assert np.all(merged[1:, 0] > merged[:-1, 1])
    assert float(np.sum(merged[:, 1] - merged[:, 0])) == pytest.approx(covered)


def test_device_work_falls_inside_the_steps_that_wait_for_it(tr):
    (_, lo, hi), = tr.spans_named("bench.window")
    busy = tr.busy_ns(lo, hi)
    assert 0 < busy < hi - lo
    steps = tr.spans_named("bench.step")
    assert steps and all(lo <= s[1] and s[2] <= hi for s in steps)
    # a step returns with its answers on the host, so it holds device time
    assert sum(tr.busy_ns(s[1], s[2]) > 0 for s in steps) == len(steps)
    idle = tr.idle_by_span(lo, hi, lambda s: None if s[0] == "bench.window" else s[0])
    assert 0 < sum(idle.values()) <= (hi - lo) - busy + 1.0
