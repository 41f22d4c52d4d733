"""``tools/limits_probe.py``, the on-chip readings for the limits of
``correct`` in the two-group cells, rehearsed on the CPU over each cell's
toy configuration (float32, so the reference as it is agrees to rounding).
What the probe's controls must show for the limits to be set between them:
the reference as it is agrees, coarser products and a broken ring do not,
and the timed path's statistic tells the model's own greedy tokens from
random ones."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELLS = {"mimo-v2-agent-decode": "mimo_toy.json",
         "laguna-repo-agent-decode": "laguna_toy.json"}


def probe():
    spec = importlib.util.spec_from_file_location(
        "limits_probe", os.path.join(ROOT, "tools", "limits_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_probe_reads_both_controls_at_toy_size(cell, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", [
        "limits_probe.py", "--workload", cell, "--paths", "cached", "timed",
        "--seeds", "3300000013", "--emit", "6", "--config",
        os.path.join(ROOT, "benchmark", "tests", CELLS[cell])])
    probe().main()
    out = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            out.update(json.loads(line))
    cached, timed = out["cached"], out["timed"]
    assert cached["as_is"]["logits_err"] < 1e-4
    assert cached["as_is"]["picks_agree"] == 1.0
    assert cached["bf16"]["logits_err"] < cached["float8"]["logits_err"]
    assert cached["ring_two_short"]["logits_err"] > 0.1
    assert all(cached[m] > 0.02 for m in ("window_minus", "window_plus"))
    assert timed["emitted"] == 6 and timed["prompt"] > 128
    assert timed["as_is"][0] < 0.01
    assert timed["random_stream"][1] > 1.0


def test_probe_reads_the_conv_state_controls_at_toy_size(monkeypatch,
                                                         capsys):
    """The conv-state cell: its check's context holds a prefix hit, and
    the controls are the conv state zeroed at that cut (through the cache,
    and on the tokens greedy emits from it) besides the coarser products
    and each published term broken."""
    monkeypatch.setattr(sys, "argv", [
        "limits_probe.py", "--workload", "lfm2-tool-agent-decode", "--paths",
        "cached", "timed", "--seeds", "3300000013", "--emit", "6",
        "--config", os.path.join(ROOT, "benchmark", "tests",
                                 "lfm2_toy.json")])
    probe().main()
    out = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            out.update(json.loads(line))
    cached, timed = out["cached"], out["timed"]
    assert cached["as_is"]["logits_err"] < 1e-4
    assert cached["as_is"]["picks_agree"] == 1.0
    assert cached["bf16"]["logits_err"] < cached["float8"]["logits_err"]
    assert cached["state_zeroed_at_cut"]["logits_err"] > 0.03
    assert "ring_two_short" not in cached
    assert all(cached[m] > 0.03 for m in ("qk_norm", "conv_flip",
                                          "conv_gate"))
    assert timed["emitted"] == 6 and timed["prompt"] == 150
    assert timed["as_is"][0] < 0.01
    assert timed["random_stream"][1] > 1.0
    assert {"bias", "state_zeroed_at_cut"} <= set(timed)
