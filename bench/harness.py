"""One run of one cell: build, warm up, drive the open loop, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration as it is run, and
  ``bench/configs/<config>.py`` beside it: its catalog generator
  (``tables(cfg, seed)``) and its host reference builder
  (``incidence(tables)``, see :mod:`reference`);
- ``bench/traffic/<traffic>.json``: the mix's parameters (:mod:`traffic`);
- ``bench/layer_metrics/<metric>.py``: ``read(run) -> float | None``.

The window is a wall-clock open loop in one thread: admit every request
that is due (a result-cache hit is answered there), run one
``GraphServingTier.step``, repeat.  A request is timed from its due time
to the moment its answer is on the host.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reference  # noqa: E402
import roofline  # noqa: E402
import trace as trace_reader  # noqa: E402
import traffic  # noqa: E402

# requests still unanswered this long after the window closes never come
DRAIN_LIMIT_S = 60.0
RESULTS = ROOT / "results"
# JAX's event for one program built: compiled, or loaded from the cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _path(kind: str, name: str, search) -> Path:
    """``<base>/<kind>/<name>`` in the first of ``search`` that has it."""
    for base in search:
        if (Path(base) / kind / name).exists():
            return Path(base) / kind / name
    raise FileNotFoundError(f"no {kind}/{name} under {[str(b) for b in search]}")


def load_config(name: str, search=(BENCH,)):
    """``(cfg, module)``: the configuration file and the code beside it."""
    cfg = json.loads(_path("configs", f"{name}.json", search).read_text())
    return cfg, _module(_path("configs", f"{name}.py", search))


def load_mix(name: str, search=(BENCH,)) -> dict:
    return json.loads(_path("traffic", f"{name}.json", search).read_text())


def load_reader(metric: str, search=(BENCH,)):
    return _module(_path("layer_metrics", f"{metric}.py", search))


# ---------------------------------------------------------------------------
# Spans: the bench's own host clock, mirrored into the profiler's trace
# ---------------------------------------------------------------------------

class Spans:
    def __init__(self, traced: bool = False):
        self.traced = traced
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.traced:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = contextlib.nullcontext()
        t = time.perf_counter()
        with ctx:
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t


# ---------------------------------------------------------------------------
# Build: catalog -> condensed extraction -> DEDUP-C correction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Built:
    tables: dict
    graph: object
    correction: object
    seconds: dict
    shape: dict


def build(cfg: dict, module, seed: int, spans: Optional[Spans] = None) -> Built:
    from repro.core import dedup
    from repro.core.extract import extract
    from repro.core.relational import Catalog, Table

    spans = spans or Spans()
    with spans("bench.build.catalog"):
        tables = module.tables(cfg, seed)
        catalog = Catalog([Table(t, cols) for t, cols in tables.items()])
    with spans("bench.build.extract"):
        graph = extract(catalog, cfg["query"], mode="condensed").graph
    with spans("bench.build.correction"):
        correction = dedup.build_correction_streaming(graph)
    shape = {
        "n_real": int(graph.n_real),
        "chains": [
            {"virtual_layers": [int(s) for s in c.layer_sizes],
             "edges": [int(e.n_edges) for e in c.edges]}
            for c in graph.chains
        ],
        "direct_edges": 0 if graph.direct is None else int(graph.direct.n_edges),
        "correction_triples": int(correction.nnz),
    }
    if shape["direct_edges"]:
        raise ValueError("the PPR work count covers chains only")
    seconds = {k.split(".")[-1]: v for k, v in spans.seconds.items()}
    return Built(tables, graph, correction, seconds, shape)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Step:
    kind: str
    width: int
    fill: int
    t0: float   # seconds after the window opened
    t1: float
    busy_s: Optional[float] = None   # device-busy time inside, from a trace


@dataclasses.dataclass
class Window:
    done: np.ndarray     # answer on the host, seconds after open; nan = never
    started: np.ndarray  # admitted (cache hit) or its batch began
    values: dict         # qid -> answer, for the checked requests
    steps: list
    max_lag_s: float     # how late the generator admitted a due request
    closed_s: float      # when the last answer came


def drive(tier, tenant: str, sched: traffic.Schedule, seconds: float,
          spans: Spans, drain_limit: float = DRAIN_LIMIT_S) -> Window:
    from repro.serve.tier import ServeRequest

    n = sched.due.size
    done = np.full(n, np.nan)
    started = np.full(n, np.nan)
    keep = set(int(q) for q in sched.checked)
    values, steps = {}, []
    max_lag = 0.0
    i = 0
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0  # noqa: E731
    with spans("bench.window"):
        while True:
            now = clock()
            if i < n and sched.due[i] <= now:
                with spans("bench.admit"):
                    while i < n and sched.due[i] <= now:
                        max_lag = max(max_lag, now - sched.due[i])
                        res = tier.submit(ServeRequest(
                            qid=i, tenant=tenant, kind=sched.kinds[i],
                            node=int(sched.nodes[i]),
                            arrival_time=float(sched.due[i]),
                        ))
                        if res is not None:
                            done[i] = started[i] = clock()
                            if i in keep:
                                values[i] = res.value
                        i += 1
                        now = clock()
            if tier.n_pending:
                if now > seconds + drain_limit:
                    break
                a = clock()
                with spans("bench.step"):
                    results = tier.step()
                b = clock()
                for res in results:
                    done[res.qid] = b
                    started[res.qid] = a
                    if res.qid in keep:
                        values[res.qid] = res.value
                r = results[0] if results else None
                steps.append(Step(r.kind, r.batch_width, r.batch_fill, a, b) if r
                             else Step("", 0, 0, a, b))
            elif i < n:
                with spans("bench.wait_arrival"):
                    time.sleep(max(0.0, sched.due[i] - clock()))
            else:
                break
    return Window(done, started, values, steps, max_lag,
                  float(np.nanmax(done)) if np.isfinite(done).any() else 0.0)


def warm(tier, tenant: str, kinds, n_nodes: int, spans: Spans) -> None:
    """Every kind of the mix at every bucket width, then a clean cache."""
    from repro.serve.tier import ServeRequest

    qid = -1
    for k, kind in enumerate(sorted(kinds)):
        for w, width in enumerate(tier.bucket_widths):
            name = "bench.warm.first_step" if k == w == 0 else "bench.warm"
            # an answer cached by the last batch would narrow this one
            tier.invalidate_results()
            for j in range(width):
                tier.submit(ServeRequest(qid, tenant, kind, j % n_nodes))
                qid -= 1
            with spans(name):
                done = tier.drain()
            if not done or done[0].batch_width != width:
                raise RuntimeError(f"warm-up of {kind} ran no batch of width {width}")
    tier.invalidate_results()


def nearest_rank(x: np.ndarray, q: float) -> float:
    """The ``q`` quantile by nearest rank; nan (never answered) ranks last."""
    s = np.sort(np.where(np.isnan(x), np.inf, x))
    return float(s[max(math.ceil(q * s.size) - 1, 0)])


# ---------------------------------------------------------------------------
# Checking answers against the reference
# ---------------------------------------------------------------------------

def check(ref: reference.Reference, sched: traffic.Schedule, win: Window,
          tier_params: dict, limits: dict) -> dict:
    """Each compared number beside its limit (see :func:`passed`)."""
    gaps, wrong = {}, {"bfs": 0, "common_neighbors": 0}
    ppr_q = [int(q) for q in sched.checked if sched.kinds[q] == "ppr"]
    if ppr_q:
        want = ref.ppr(sched.nodes[ppr_q], tier_params["damping"], tier_params["ppr_iters"])
        for j, q in enumerate(ppr_q):
            if q in win.values:
                gaps[q] = reference.ppr_gap(win.values[q], want[:, j])
    for q in sched.checked:
        q = int(q)
        kind = sched.kinds[q]
        if kind == "ppr" or q not in win.values:
            continue
        node = int(sched.nodes[q])
        want = ref.bfs(node) if kind == "bfs" else ref.common_neighbors(node)
        if not np.array_equal(win.values[q], want.astype(np.float32)):
            wrong[kind] += 1
    checks = {"unanswered": {"value": int(np.isnan(win.done).sum()), "limit": 0}}
    kinds = set(sched.kinds)
    if "ppr" in kinds:
        checks["ppr_gap"] = {"value": max(gaps.values(), default=0.0),
                             "limit": limits["ppr_gap"]}
    for kind in ("bfs", "common_neighbors"):
        if kind in kinds:
            checks[f"{kind}_wrong"] = {"value": wrong[kind], "limit": 0}
    return checks


def passed(checks: dict) -> bool:
    """``correct``: every compared number inside its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader may read."""
    shape: dict
    setup: dict          # set-up span seconds by name
    window: Window
    schedule: traffic.Schedule
    cache: dict          # result-cache hits and misses over the window
    tier: dict           # the tier's settings
    trace: Optional[object]   # trace.Trace, in a traced run
    window_ns: Optional[tuple]  # the traced window's (start, end)
    peak: Optional[dict]


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t_start: float, bench: Optional[dict] = None, search=(BENCH,),
             log=print) -> dict:
    """One run of ``workload``: the result line as a dict.  ``bench`` is
    the parsed ``BENCHMARK.json``; configurations, mixes and readers are
    looked up by name in the directories of ``search``, in order."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.tier import GraphServingTier

    bench = bench or load_benchmark()
    cell = find(bench["workloads"], workload, "workload")
    cfg, module = load_config(cell["config"], search)
    mix = load_mix(cell["traffic"], search)
    devices = jax.devices()
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(json.dumps({"phase": "device", "platform": devices[0].platform,
                    "device_kind": devices[0].device_kind,
                    "count": len(devices), "cell": workload, "seed": seed}))

    spans = Spans()
    built = build(cfg, module, seed, spans)
    tier = GraphServingTier()
    tier.add_tenant(cfg["name"], built.graph, correction=built.correction,
                    packed=True)
    warm(tier, cfg["name"], mix["kinds"], built.shape["n_real"], spans)
    from repro.core import engine

    log(json.dumps({
        "phase": "setup", "shape": built.shape,
        "seconds": spans.seconds,
        "device_graph_bytes": tier.budget.resident_bytes,
        "kernel_dispatch_count": engine.KERNEL_DISPATCH_COUNT,
        "kernel_standdown_count": dict(engine.KERNEL_STANDDOWN_COUNT),
    }))
    sched = traffic.schedule(mix, module.node_of(cfg, seed), seed, seconds)
    hits0, misses0 = tier.result_stats.hits, tier.result_stats.misses
    trace_dir = RESULTS / "trace" / workload
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles = []

    def on_compile(event, duration, **kwargs):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    setup_s = time.perf_counter() - t_start
    try:
        win = drive(tier, cfg["name"], sched, seconds, Spans(traced))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    if traced:
        jax.profiler.stop_trace()
    stats = devices[0].memory_stats() or {}
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices)
    cache = {"hits": tier.result_stats.hits - hits0,
             "misses": tier.result_stats.misses - misses0}
    tier_params = {"ppr_iters": tier.ppr_iters, "damping": tier.damping}
    due_in_window = sched.due.size
    latency_ms = (win.done - sched.due) * 1e3
    log(json.dumps({
        "phase": "window", "requests": due_in_window,
        "arrivals_sha1": hashlib.sha1(sched.due.tobytes()).hexdigest()[:12],
        "steps": len(win.steps), "generator_max_lag_s": win.max_lag_s,
        "last_answer_s": win.closed_s, "result_cache": cache,
        "bytes_in_use": stats.get("bytes_in_use"),
        "programs_built_in_window": len(compiles),
        "build_s_in_window": sum(compiles),
    }))
    del tier
    gc.collect()

    t = time.perf_counter()
    ref = reference.Reference(*module.incidence(built.tables))
    checks = check(ref, sched, win, tier_params, cfg["limits"])
    correct = passed(checks)
    log(json.dumps({"phase": "check", "seconds": time.perf_counter() - t}))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": int(due_in_window),
              "failed": int(np.isnan(win.done).sum())}
    if not traced:
        result["metrics"] = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "query_p50_ms": {"value": nearest_rank(latency_ms, 0.50), "unit": "ms"},
            "query_p95_ms": {"value": nearest_rank(latency_ms, 0.95), "unit": "ms"},
            "peak_hbm_gb": {"value": peak_bytes / 1e9, "unit": "GB"},
        }
    else:
        tr = trace_reader.read(trace_reader.newest_xplane(str(trace_dir)))
        windows = tr.spans_named("bench.window")
        window_ns = (windows[0][1], windows[0][2]) if windows else None
        traced_steps = tr.spans_named("bench.step")
        if tr.devices and len(traced_steps) == len(win.steps):
            for st, sp in zip(win.steps, traced_steps):
                st.busy_s = tr.busy_ns(sp[1], sp[2]) / 1e9
        try:
            peak = roofline.peaks(devices[0].device_kind)
        except KeyError:
            if devices[0].platform == "tpu":
                raise
            peak = None
        run = Run(built.shape, dict(spans.seconds), win, sched, cache,
                  tier_params, tr, window_ns, peak)
        metrics = {}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = load_reader(m["name"], search).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if window_ns is not None and tr.devices:
            device["busy_s"] = tr.busy_ns(*window_ns) / 1e9
            device["window_s"] = (window_ns[1] - window_ns[0]) / 1e9
            result["breakdown"] = breakdown(tr, window_ns, win)
    result["device"] = device
    result["checks"] = checks
    return result


def breakdown(tr, window_ns: tuple, win: Window) -> dict:
    ops = sorted(tr.op_time.items(), key=lambda kv: -kv[1])[: trace_reader.TOP]
    steps = iter(win.steps)

    def label(span):
        name = span[0]
        if name == "bench.step":
            st = next(steps, None)
            return f"bench.step.{st.kind}" if st is not None else name
        return name if name != "bench.window" else None

    gaps = tr.idle_by_span(*window_ns, label)
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[: trace_reader.TOP]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in top]}
