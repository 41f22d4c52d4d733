"""The yardstick's own arithmetic, checked without a chip.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
(tier-1 collects only ``tests/``).
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import common, flops, trace_reduce as tr   # noqa: E402

general = common.load_module("traffic", "general.py")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- trace_reduce: interval arithmetic on synthetic events -------------------

EVENTS = [("a", 0, 10), ("b", 5, 10), ("a", 30, 5), ("c", 100, 1)]


def test_union_and_busy():
    assert tr.union((s, d) for _, s, d in EVENTS) == \
        [[0, 15], [30, 35], [100, 101]]
    assert tr.busy_ns(EVENTS) == 21
    assert tr.busy_ns([]) == 0


def test_nested_events_count_once():
    # a while loop's event spans its body's events on the same line
    nested = [("while", 0, 100), ("fusion", 10, 20), ("fusion", 40, 10),
              ("copy", 42, 3), ("after", 100, 5)]
    assert tr.busy_ns(nested) == 105
    assert sorted(tr.top_k(nested, 9)) == [
        ("after", 5, 1), ("copy", 3, 1), ("fusion", 27, 2), ("while", 70, 1)]


def test_top_k_and_gaps():
    flat = [("a", 0, 10), ("b", 10, 10), ("a", 30, 5), ("c", 100, 1)]
    assert tr.top_k(flat, 2) == [("a", 15, 2), ("b", 10, 1)]
    assert tr.gaps(EVENTS, 5) == [(35, 65), (15, 15)]
    spans = [("tick", 0, 200), ("submit", 40, 10), ("other", 500, 5)]
    assert tr.name_gaps([(35, 65), (15, 15), (300, 10)], spans) == \
        [("tick", 65), ("tick", 15), ("(no span)", 10)]
    assert tr.name_gaps([(40, 8)], spans) == [("submit", 8)]


def test_idle_share_from_reduce():
    loaded = {"devices": {"/device:TPU:0": {
        "ops": [("%fusion.1 = bf16[8] fusion(x)", 0, 400_000_000),
                ("%custom-call.2 = bf16[8] custom-call(y)", 600_000_000,
                 400_000_000)],
        "modules": [("jit_block_fn(123)", 0, 1_000_000_000)]}},
        "host_spans": [("tick", 0, 1_000_000_000)]}
    out = tr.reduce(loaded)
    assert out["busy_s"] == pytest.approx(0.8)
    assert out["modules"] == {"jit_block_fn": (1, 1.0)}
    assert out["device_ops"][0][0] in ("fusion.1", "custom-call.2")
    assert out["idle_gaps"] == [["tick", pytest.approx(0.2)]]
    assert 1 - out["busy_s"] / 1.0 == pytest.approx(0.2)


XSPACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "%paged.1 = f32[] custom-call()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_block_fn(99)" } } }
planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "tick" } }
  event_metadata { key: 2 value { id: 2 name: "unrelated" } } }
"""


def test_xplane_adapter_on_a_text_fixture():
    from jax.profiler import ProfileData
    loaded = tr.load(ProfileData.from_text_proto(XSPACE), ("tick",))
    dev = loaded["devices"]["/device:TPU:0"]
    assert [(n.split(" = ")[0], d) for n, _, d in dev["ops"]] == \
        [("%fusion.7", 2000), ("%paged.1", 1000)]
    assert dev["ops"][1][1] - dev["ops"][0][1] == 5000      # ns apart
    assert [n for n, _, _ in loaded["host_spans"]] == ["tick"]
    out = tr.reduce(loaded)
    assert out["busy_s"] == pytest.approx(3e-6)
    assert out["modules"] == {"jit_block_fn": (1, pytest.approx(6e-6))}
    assert out["idle_gaps"] == [["tick", pytest.approx(3e-6)]]


# -- flops.py against hand-worked numbers -----------------------------------

def _config(name):
    return common.load_json("configs", name + ".json")


@pytest.mark.parametrize("name,per_layer,emb_head", [
    # q,o 2*4096*4096 + k,v 2*4096*1024 + mlp 3*4096*14336 + norms 2*4096
    ("mistral-7b-v0.3", 33_554_432 + 8_388_608 + 176_160_768 + 8_192,
     2 * 4096 * 32768),
    # k,v 2*4096*512, mlp 3*4096*11008
    ("yi-1.5-6b", 33_554_432 + 4_194_304 + 135_266_304 + 8_192,
     2 * 4096 * 64000),
])
def test_parameter_counts(name, per_layer, emb_head):
    c = _config(name)
    assert flops.layer_params(c) == per_layer == \
        c["sizes"]["parameters_per_layer"]
    assert round(per_layer / 1e6, 1) in (218.1, 173.0)
    assert flops.embedding_params(c) + flops.head_params(c) \
        - c["hidden_size"] == emb_head == c["sizes"]["embedding_plus_head"]
    assert flops.kv_bytes_per_token_per_layer(c) == \
        2 * c["num_key_value_heads"] * 128 * 2


def test_kv_and_decode_bytes_mistral():
    c = _config("mistral-7b-v0.3")
    assert flops.kv_bytes_per_token_per_layer(c) == 4096        # 4 KiB
    weights = (16 * 218_112_000 + 4096 * 32768 + 4096) * 2
    assert flops.decode_step_bytes(c, 0) == weights
    assert round(weights / 1e9, 2) == 7.25
    assert flops.decode_step_bytes(c, 1000) - weights == 1000 * 4096 * 16
    assert flops.total_params(c) == c["sizes"]["parameters_total"]


def test_train_flops_yi():
    c = dict(_config("yi-1.5-6b"), num_hidden_layers=6)
    matmul = 6 * (173_023_232 - 8_192) + 4096 * 64000
    attn = 6 * 6 * 4096 * 4096            # layers * 6 * s * (heads*head_dim)
    assert flops.train_flops_per_token(c, 4096) == 6.0 * matmul + attn
    assert round(flops.train_flops_per_token(c, 4096) / 1e9, 1) == 8.4


# -- the generator ----------------------------------------------------------

def _cell(name):
    return common.load_json("workloads", name + ".json")


@pytest.mark.parametrize("cell", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "workloads"))
    if _cell(f[:-5])["kind"] == "serve"))
def test_generator_reproduces_and_permutes(cell):
    params = _cell(cell)["traffic"]
    big = 3_000_000_019                     # above 2**31, as the driver's are
    a, b = (general.Traffic(params, big, 32768) for _ in range(2))
    other = general.Traffic(params, big + 1, 32768)
    n = params["pool"]
    first = params.get("first_wave", 0)
    ra = [a.request(i) for i in range(first, first + n)]
    rb = [b.request(i) for i in range(first, first + n)]
    ro = [other.request(i) for i in range(first, first + n)]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(ra, rb))
    # another seed: the same set of sizes, in another order
    if first % n == 0:
        def sizes(rs):
            return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in rs)
        assert sizes(ra) == sizes(ro)
    assert [len(r["prompt"]) for r in ra] != [len(r["prompt"]) for r in ro]
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 32768
               for r in ra)
    assert [a.due(i) for i in range(n)] == [b.due(i) for i in range(n)]


def _poisson(rate, **arrivals):
    return {"arrivals": {"process": "poisson", "rate_per_s": rate,
                         **arrivals},
            "prompt_len": {"dist": "bounded_pareto", "alpha": 1.0,
                           "lo": 128, "hi": 3072},
            "output_len": {"dist": "bounded_pareto", "alpha": 1.2,
                           "lo": 32, "hi": 512},
            "pool": 128, "shape_seed": 2}


@pytest.mark.parametrize("arrivals", [
    {}, {"burst": {"factor": 4, "on_s": 4, "period_s": 20}}])
def test_open_loop_rate_matches_the_asked_one(arrivals):
    t = general.Traffic(_poisson(3.5, **arrivals), 11, 1000)
    due = [t.due(i) for i in range(768)]         # six whole pools
    assert all(x < y for x, y in zip(due, due[1:]))
    assert 768 / due[-1] == pytest.approx(3.5, rel=0.02)
    if arrivals:                # 4x the rate inside the first 4 s of 20
        inside = sum(1 for d in due if d % 20 < 4)
        assert inside / len(due) == pytest.approx(0.8, abs=0.05)
    else:                       # one pool of gaps spans exactly pool / rate
        assert due[127] == pytest.approx(128 / 3.5)


def test_bounded_pareto_pool_has_the_stated_shape():
    q = general.quantiles(_poisson(1)["prompt_len"], 4096)
    assert q.min() >= 128 and q.max() <= 3072
    assert np.median(q) == pytest.approx(245, rel=0.03)
    assert q.mean() == pytest.approx(420, rel=0.05)
    assert np.percentile(q, 95) == pytest.approx(1500, rel=0.1)


# -- BENCHMARK.json ----------------------------------------------------------

def test_benchmark_json_names_files_that_exist():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
    kinds = set()
    for w in BENCH["workloads"]:
        cell = _cell(w["name"])
        assert cell["config"] == w["config"]
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "kinds", cell["kind"] + ".py"))
        if "generator" in cell:
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "traffic", cell["generator"] + ".py"))
        kinds.add(cell["kind"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in e2e
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads",
                                                               cells))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_names_and_units_use_only_the_allowed_characters():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in (BENCH["configs"], BENCH["workloads"],
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        assert len({x["name"] for x in group}) == len(group)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for root, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


# -- run.py end to end at toy sizes -----------------------------------------

@pytest.mark.parametrize("cell,trace", [
    (w["name"], t) for w in BENCH["workloads"] for t in (0, 1)])
def test_rehearsal_prints_the_contract_line(cell, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "x"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}, line          # no breakdown off the chip
    assert line["correct"] is True and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    want = {m["name"] for m in BENCH["per_layer" if trace else "end_to_end"]
            if cell in m.get("workloads", [cell])}
    device_only = {m["name"] for m in BENCH["per_layer"]
                   if m["source"] == "device_trace"
                   or m["name"].startswith(("hbm_peak", "mfu_pct"))}
    assert set(line["metrics"]) == want - (device_only if trace else set())
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in line["metrics"].values())


def test_open_loop_cell_added_as_data_only(tmp_path):
    """The chat cell PR 24 could not prove on the chip, added the way a
    later PR will add it: one workloads file and entries in BENCHMARK.json,
    no edit to the harness. Keeps the open-loop path rehearsed."""
    import shutil
    fix = json.load(open(os.path.join(HERE, "chat_cell.json")))
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    add, name = fix["benchmark_json"], fix["benchmark_json"]["workload"]["name"]
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(add["workload"])
    bench["end_to_end"] += add["end_to_end"]
    bench["per_layer"] += add["per_layer"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in add["also_reports"]:
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmark" / "workloads" / (name + ".json")).write_text(
        json.dumps(fix["cell"]))
    for trace, want in ((0, {"ttft_ms_p90", "gap_ms_p95", "setup_s"}),
                        (1, {"gen_late_ms_p90", "queue_wait_ms_p90",
                             "tick_ms_p95.serve"})):
        out = subprocess.run(
            [sys.executable, str(tmp_path / "benchmark" / "run.py"),
             "--workload", name, "--seed", "3000000019", "--seconds", "4",
             "--trace", str(trace), "--rehearse", "--rate-per-s", "6"],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["correct"] is True and line["attempted"] >= 10
        assert line["failed"] == 0 and set(line["metrics"]) == want


def test_no_tpu_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "{" not in out.stdout
