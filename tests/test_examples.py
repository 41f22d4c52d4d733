"""CPU smoke for the user-facing examples/ scripts — the migration
surface a reference user tries first must not rot. Full/weekly lane
only (full_lane.txt): five subprocess jax startups (~3-4 min).

Each example documents its own CPU smoke invocation in its docstring;
these run exactly those."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [
    ("train_llama.py", ["--cpu", "--tiny", "--steps", "2",
                        "--batch", "2", "--seq", "32"]),
    ("generate.py", ["--cpu", "--tiny", "--max-new", "4"]),
    ("finetune_vision.py", ["--cpu", "--epochs", "1"]),
    ("ps_recsys.py", []),
    ("text_to_image.py", []),
]


@pytest.mark.parametrize("script,args",
                         CASES, ids=[c[0] for c in CASES])
def test_example_cpu_smoke(script, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ("--xla_llvm_disable_expensive_passes=true"
                        " --xla_backend_optimization_level=0")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, (script, p.stdout[-1500:],
                               p.stderr[-1500:])
