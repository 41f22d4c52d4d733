"""Test env: 8 virtual CPU devices so mesh/sharding tests run without TPU
hardware (SURVEY §4: the reference tests multi-device logic with
multi-process Gloo-on-CPU; here one process with 8 XLA host devices).

The suite runs on the CPU backend: ``JAX_PLATFORMS=cpu`` is pinned here
before jax is imported, whatever the caller exported.

PT_TPU_TESTS=1 skips the CPU pinning so the on-hardware kernel tests
(tests/test_pallas_tpu.py) run against the real chip, one process:
    PT_TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py -q"""
import os

_ON_TPU = os.environ.get("PT_TPU_TESTS") == "1"

if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if not _ON_TPU and "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# Tests check numerics/parity, not codegen quality: skip expensive LLVM
# passes so the big model-zoo graphs compile ~30% faster on CPU.
if not _ON_TPU and "xla_llvm_disable_expensive_passes" not in flags:
    flags += (" --xla_llvm_disable_expensive_passes=true"
              " --xla_backend_optimization_level=0")
os.environ["XLA_FLAGS"] = flags.strip()

# transformers (the HF parity oracles) probes TensorFlow on import —
# ~11s of the suite for a framework no test uses. USE_TF=0 makes it
# torch-only before any test file triggers the import.
os.environ.setdefault("USE_TF", "0")
os.environ.setdefault("TRANSFORMERS_NO_ADVISORY_WARNINGS", "1")

# autotune isolation: kernels consult the block-size tuning table at
# trace time (ops/pallas/autotune.py), so ANY reachable table — the
# default ~/.cache path (e.g. written by autotune.run_autotune) OR
# an inherited PT_TUNE_TABLE export — would make block choices, and
# therefore compiled programs and timing-sensitive pins,
# machine-dependent. Pin the suite unconditionally to a path that never
# exists; autotune tests monkeypatch their own tmp tables.
os.environ["PT_TUNE_TABLE"] = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    ".tune_table_isolated.json")

import jax

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")
    assert not jax.config.jax_platforms or \
        jax.config.jax_platforms == "cpu"

# Persistent compile cache: repeat suite runs skip recompilation entirely.
from paddle_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache(min_compile_time_secs=0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield


_SOAK_CHILD = """
import json, sys
import jax
jax.config.update("jax_enable_compilation_cache", False)
from paddle_tpu.serving import microbench
out = getattr(microbench, sys.argv[1])(**json.loads(sys.argv[2]))
print("SOAK_JSON " + json.dumps(out))
"""


@pytest.fixture
def run_soak():
    """Run one entry point of ``serving/microbench.py`` the way the
    ``tools/*.sh`` that drive it do: a plain child process with the
    persistent compile cache off (the soaks build fresh paged backends
    beside each other — tests/test_resilience.py ``_no_compile_cache``
    says why that stays out of a pytest process). Returns its dict."""
    import json
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(name, **kw):
        p = subprocess.run(
            [sys.executable, "-c", _SOAK_CHILD, name, json.dumps(kw)],
            capture_output=True, text=True, timeout=600, cwd=root,
            env=dict(os.environ, PYTHONPATH=root))
        lines = [l for l in p.stdout.splitlines()
                 if l.startswith("SOAK_JSON ")]
        assert p.returncode == 0 and lines, p.stdout[-2000:] + \
            p.stderr[-3000:]
        return json.loads(lines[-1][len("SOAK_JSON "):])
    return run


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-wall tests")


# ---------------------------------------------------------------------------
# Quick/full lanes (VERDICT r4 #7). The suite is XLA-CPU-compile-bound
# (~10s per distinct conv/transformer graph on the 1-core host; measured
# r5: fuzz files are cheap, model-compile parity tests are the cost). The
# default lane deselects — NOT skips — the tests in tests/full_lane.txt:
# the most compile-expensive parity/oracle tests whose capability is
# also exercised by cheaper tests or by chip_smoke.py on the chip.
# PT_FULL=1 runs everything (the weekly/full lane). Deselection is
# announced in the
# header so a lower test count is never mistaken for lost coverage.
# ---------------------------------------------------------------------------
def _full_lane_prefixes():
    path = os.path.join(os.path.dirname(__file__), "full_lane.txt")
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line and not line.startswith("#"):
                    out.append(line.split()[0])
    except OSError:
        pass
    return out


def pytest_collection_modifyitems(config, items):
    if os.environ.get("PT_FULL") == "1":
        return
    prefixes = _full_lane_prefixes()
    if not prefixes:
        return
    kept, deselected = [], []
    for it in items:
        nodeid = it.nodeid.replace(os.sep, "/")
        if any(nodeid.startswith(p) for p in prefixes):
            deselected.append(it)
        else:
            kept.append(it)
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = kept


def pytest_report_header(config):
    # jax/jaxlib versions on every run's record: the per-re-anchor
    # "did a jaxlib upgrade fix the heap landmine?" check needs a paper
    # trail of which jaxlib each tier-1 result was produced under
    import importlib.metadata as _md
    try:
        _jaxlib = _md.version("jaxlib")
    except _md.PackageNotFoundError:
        _jaxlib = "unknown"
    lines = [f"jax {jax.__version__} / jaxlib {_jaxlib} "
             f"(tier-1 results are judged per-jaxlib; see ROADMAP env "
             "note)"]
    # the known environment landmine (documented in test_resilience.py):
    # jax's persistent compile cache + the xdist/randomly plugins
    # corrupts the native heap when a SECOND paged step backend compiles
    # in one process (glibc double-free at exit). Tier-1 runs with
    # `-p no:xdist -p no:randomly` and is immune — warn when a run is
    # NOT in that safe configuration so a native crash is attributable.
    risky = [p for p in ("xdist", "randomly")
             if config.pluginmanager.has_plugin(p)]
    if risky:
        lines.append(
            "WARNING: plugin(s) %s active with the persistent jax "
            "compile cache — known native-heap landmine when a second "
            "paged serving backend compiles in-process (glibc "
            "double-free at exit). Tier-1 passes -p no:xdist "
            "-p no:randomly; re-check on each jaxlib upgrade."
            % "/".join(risky))
    if os.environ.get("PT_FULL") == "1":
        lines.append("lane: FULL (every test; weekly lane)")
        return lines
    n = len(_full_lane_prefixes())
    lines.append(f"lane: quick — tests/full_lane.txt lists {n} "
                 "compile-heavy groups deselected here; PT_FULL=1 runs "
                 "all")
    return lines
