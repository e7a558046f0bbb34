"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases, at a tiny cut of the catalog, pass their own checks (the kernel
phase in Pallas interpret mode, the four-chip phase on four virtual CPU
devices)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
SMOKE = os.path.join(REPO, "chip_smoke.py")
TINY = 0.0005   # 800 authors, 1,500 publications


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module
    spec.loader.exec_module(module)
    yield module
    del sys.modules["chip_smoke"]


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.build(TINY)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, SMOKE], capture_output=True, text=True,
        timeout=300, cwd=REPO, env=env,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


def test_build_reports_the_cut(smoke, tiny):
    d = smoke.describe(tiny, TINY)
    assert (d["authors"], d["pubs"]) == (800, 1500)
    assert d["author_pub_rows"] == tiny.graph.chains[0].edges[0].n_edges
    assert set(d["seconds"]) == {"catalog", "extract", "correction"}
    json.dumps(d)


def test_serving_phase_checks_every_answer(smoke, tiny):
    report = smoke.serving_phase(tiny)
    for kind in ("bfs", "ppr", "common_neighbors"):
        assert report[kind]["answers_checked"] == smoke.N_BATCHES * smoke.BATCH
    assert report["device_graph_bytes"] > 0
    json.dumps(report)


def test_host_reference_catches_a_wrong_answer(smoke, tiny):
    ref = smoke.HostReference(tiny)
    node = 0
    right = ref.bfs(node).astype("float32")
    wrong = right.copy()
    wrong[wrong == 1.0] = 2.0
    smoke.check_answers("bfs", [node], [right], ref, None)
    with pytest.raises(AssertionError, match="bfs node 0"):
        smoke.check_answers("bfs", [node], [wrong], ref, None)


def test_kernel_phase_matches_segment_path(smoke, tiny):
    report = smoke.kernel_phase(tiny)
    for op in ("sum", "fused", "min"):
        assert report[op]["kernel_dispatch_count"] > 0
        assert report[op]["kernel_standdown_count"] == {}


FOUR_DEVICES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
sys.modules["chip_smoke"] = smoke
spec.loader.exec_module(smoke)
report = smoke.four_chip_phase(smoke.build(float(sys.argv[2])), jax.devices())
assert report["devices_spanned"] == [0, 1, 2, 3], report
print("FOUR_OK", report["max_abs_diff"])
"""


def test_four_chip_phase_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES, SMOKE, str(TINY)],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "FOUR_OK" in proc.stdout


CACHE_SETTINGS = (
    "jax_compilation_cache_dir",
    "jax_compilation_cache_include_metadata_in_key",
    "jax_hlo_source_file_canonicalization_regex",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


def test_compile_cache_dir_is_fixed(monkeypatch):
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    settings = {k: getattr(jax.config, k) for k in CACHE_SETTINGS}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.realpath(REPO), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        for k, v in settings.items():
            jax.config.update(k, v)


def test_cached_programs_keep_their_own_scopes(tmp_path):
    """Two programs that differ in a named scope alone are two cache
    entries: the second does not load the first's executable, whose ops
    would name the first's scope in the device trace."""
    import re

    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    was = {k: getattr(jax.config, k) for k in CACHE_SETTINGS}
    try:
        compile_cache.enable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()

        def program(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.sin(x) * 3.0 + 1.0
            return f

        x = jnp.ones(16)
        texts = [jax.jit(program(s)).lower(x).compile().as_text()
                 for s in ("engine.layer", "engine.interior")]
        assert "engine.layer/" in texts[0] and "engine.interior/" in texts[1]
        assert "engine.layer/" not in texts[1]
        assert len(list(tmp_path.glob("jit_f-*-cache"))) == 2
        # source files enter the key relative to the checkout
        regex = jax.config.jax_hlo_source_file_canonicalization_regex
        engine = compile_cache.CHECKOUT / "src" / "repro" / "core" / "engine.py"
        assert re.sub(regex, "", str(engine)) == os.path.join("src", "repro", "core", "engine.py")
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
