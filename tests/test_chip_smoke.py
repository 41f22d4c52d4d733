"""chip_smoke.py off the chip: it must fail without a TPU, and its
``--tiny`` CPU rehearsal (the same control flow at toy widths, Pallas in
interpret mode) must pass — so a later PR that breaks the smoke's path
finds out here, not on a chip call. Each case is one child process: the
script picks its platform before it imports jax."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the suite's 8 virtual devices
    p = subprocess.run([sys.executable, os.path.join(ROOT, script), *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    lines = p.stdout.strip().splitlines()
    return p.returncode, lines, p.stdout[-3000:] + p.stderr[-3000:]


@pytest.mark.parametrize("script,args", [
    ("chip_smoke.py", ()),
    ("chip_smoke.py", ("--chips", "4")),
], ids=["smoke", "smoke-4"])
def test_fails_without_a_tpu_and_prints_no_result(script, args):
    rc, lines, tail = _run(script, *args)
    assert rc != 0, tail
    assert not any('"ok"' in l or l.startswith("{")
                   for l in lines), tail


@pytest.mark.parametrize("chips", [1, 4])
def test_tiny_rehearsal_passes_and_never_reports_ok(chips):
    rc, lines, tail = _run("chip_smoke.py", "--tiny", "--chips", str(chips))
    assert rc == 0, tail
    last = json.loads(lines[-1])
    assert last == {"rehearsal_ok": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": chips}}, tail
    assert not any('"ok"' in l for l in lines), tail
    assert not any("PHASE FAILED" in l for l in lines), tail
