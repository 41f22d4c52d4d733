"""CPU smoke for bench_workloads.py (PT_WORKLOADS_TINY shapes) so a
chip session never spends its window discovering an API break in the
workload-bench code paths."""
import os
import subprocess
import sys
import json

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


NAMES = ["resnet50", "bert_base", "ernie_moe", "sdxl_unet",
         "llama_serve"]


def test_workload_tiny_all():
    """All four workloads in ONE subprocess: the per-name subprocesses
    each paid a ~10s cold jax import for no isolation benefit on CPU
    (on the chip keep one point per process)."""
    env = dict(os.environ, PT_WORKLOADS_TINY="1", JAX_PLATFORMS="cpu")
    # single fake device is enough, but KEEP the fast-compile flags —
    # dropping them made every tiny XLA compile pay the full LLVM
    # pipeline (this test was 160s of the cold suite)
    env["XLA_FLAGS"] = ("--xla_llvm_disable_expensive_passes=true"
                        " --xla_backend_optimization_level=0")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench_workloads.py"), *NAMES],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    lines = [l for l in p.stdout.splitlines()
             if l.startswith("WORKLOAD ")]
    assert len(lines) == len(NAMES), (
        f"{len(lines)} WORKLOAD lines: {p.stdout[-2000:]} "
        f"{p.stderr[-2000:]}")
    for name, line in zip(NAMES, lines):
        r = json.loads(line[len("WORKLOAD "):])
        assert "error" not in r, (name, r["error"])
        # TINY mode labels resnet50 as resnet18_train_tiny_smoke
        # (provenance: a stand-in model must not carry the real label)
        assert r["workload"].startswith(name.split("_")[0][:6])
        if name == "sdxl_unet":
            assert r["infer_step_ms"] > 0 and r["train_step_ms"] > 0
        elif name == "llama_serve":
            assert r["tokens_per_sec"] > 0
            assert r["decode_compile_count"] == 1
        else:
            assert r["step_ms"] > 0
