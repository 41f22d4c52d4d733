"""The benchmark's arithmetic for the cell of a looped model: sizes from
shapes, a step's bytes counted once a PASS, the kernel's device time per
call over all cache layers, and the configuration file held to the catalog
row it was taken from."""
import argparse
import json
import os

import pytest

from benchmark import common, flops, flops_looped as fl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "Ouro-2.6B"
CELL = "ouro-reason-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_sizes_from_shapes(cfg):
    """The issue's arithmetic, from the file: a layer 51,388,416; 48 of
    them 2,466.6M; embedding + head 201.3M; final norm + gate 4,097;
    2,668.0M in all = 5.34 GB = 4.97 GiB. KV 8,192 B a token a (layer,
    pass), 1.5 MiB a token over 192 cache layers; the arenas 7.52 GiB."""
    sizes = cfg["sizes"]
    assert fl.layer_params(cfg) == 4 * 2048 * 2048 + 3 * 2048 * 5632 \
        + 4 * 2048 == sizes["layer_parameters"] == 51388416
    assert fl.layer_params(cfg) - flops.layer_params(cfg) == 2 * 2048
    assert 48 * fl.layer_params(cfg) == sizes["layers_parameters"]
    assert fl.gate_params(cfg) + 2048 == sizes["final_norm_plus_gate"] == 4097
    assert fl.total_params(cfg) == sizes["parameters_total"] == 2667974657
    assert round(fl.total_params(cfg) * 2 / 1e9, 2) \
        == sizes["weights_gb_bf16"] == 5.34
    assert round(fl.total_params(cfg) * 2 / 2 ** 30, 2) \
        == sizes["weights_gib_bf16"] == 4.97
    assert flops.kv_bytes_per_token_per_layer(cfg) == 8192 \
        == sizes["kv_bytes_per_token_per_layer_pass"]
    assert fl.cache_layers(cfg) == 192 == sizes["kv_cache_layers"]
    assert 192 * 8192 == sizes["kv_bytes_per_token"] == 1572864
    dep = cfg["deployment"]
    assert dep["num_blocks"] == 1 + dep["num_slots"] * 40
    # the longest request writes 192 + 448 - 1 positions: 40 blocks
    assert -(-(192 + 448 - 1) // 16) == 40
    arenas = 2 * 48 * 4 * dep["num_blocks"] * 16 * 16 * 128 * 2
    assert arenas == sizes["arena_bytes"] == 8078229504
    assert round(arenas / 2 ** 30, 2) == sizes["arena_gib"] == 7.52
    # what a deployment would hold: over the driver's 25% floor, under 14 GiB
    assert 0.25 * 16e9 < arenas + 2 * fl.total_params(cfg) < 14 * 2 ** 30


def test_the_tiny_models_parameters_are_the_arithmetics():
    import dataclasses
    from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
    model = OuroForCausalLM(ouro_tiny_config())
    assert model.num_params() == fl.total_params(
        dataclasses.asdict(model.config))


def test_a_decode_step_reads_the_stack_once_a_pass(cfg):
    none = fl.decode_step_bytes(cfg, 0)
    assert none == 2 * (4 * 48 * 51388416 + 2048 * 49152 + 2048 + 2049)
    # the plain count reads every weight once and 48 cache layers: a
    # quarter of the truth for the layers
    assert flops.decode_step_bytes(cfg, 0) < none / 3.8
    assert fl.decode_step_bytes(cfg, 1000) - none == 1000 * 8192 * 192
    assert fl.decode_kernel_bytes(cfg, 1000) == 1000 * 8192
    # the issue's reckoning: 19.93 GB of weights (24.3 ms at 819 GB/s) +
    # about 2,400 live tokens x 1.5 MiB = 3.8 GB: a floor of 28.9 ms
    assert 19.9e9 < none < 20.0e9
    assert 28.5 < fl.decode_step_bytes(cfg, 2400) / 819e9 * 1e3 < 29.3


def test_readers_divide_by_every_cache_layers_calls(cfg):
    """8 slots of 300 tokens: 2,400 live tokens are 19.7 MB a call; 80
    traced steps x 192 calls in 80 x 192 x 40 us read 60% of the roofline;
    the step's 23.7 GB in 36 ms read 80%; the readers decline, never raise,
    where the program has no such counters."""
    peaks = {"hbm_bytes_per_s": 819e9}
    window = {"decode_module": "jit_block_fn", "decode_block": 8,
              "kv_live_tokens_mean": 2400.0, "ut_steps_per_decode_step": 4.0,
              "ut_expected_exit_step": 0.9}
    ctx = argparse.Namespace(
        window=window, peaks=peaks, config=cfg,
        trace_summary={"modules": {"jit_block_fn": (10, 10 * 8 * 0.036)}},
        kernel_seconds={"paged_attention_decode": (15360, 15360 * 40e-6)})
    read = lambda name: common.load_module(           # noqa: E731
        "layer_metrics", name + ".py").read(ctx)
    assert read("ut_decode_kernel_roofline") == pytest.approx(
        2400 * 8192 / 819e9 / 40e-6 * 100)
    assert read("looped_decode_step_roofline") == pytest.approx(
        fl.decode_step_bytes(cfg, 2400) / 819e9 / 0.036 * 100)
    assert read("ut_expected_exit_step") == 0.9
    assert 55 < read("ut_decode_kernel_roofline") < 65
    assert 75 < read("looped_decode_step_roofline") < 85
    ctx = argparse.Namespace(window={}, trace_summary={}, peaks=None,
                             config={}, kernel_seconds=None)
    for name in ("looped_decode_step_roofline", "ut_decode_kernel_roofline",
                 "ut_expected_exit_step"):
        assert read(name) is None
    # a cell of another model (no looped counters in its window) declines
    ctx = argparse.Namespace(
        window={"decode_module": "jit_block_fn", "decode_block": 8,
                "kv_live_tokens_mean": 2400.0}, peaks=peaks, config=cfg,
        trace_summary={"modules": {"jit_block_fn": (10, 1.0)}},
        kernel_seconds={"paged_attention_decode": (160, 0.1)})
    assert read("looped_decode_step_roofline") is None
    assert read("ut_decode_kernel_roofline") is None


def test_configuration_file_keeps_every_published_key(cfg, bench):
    """The catalog row's ``config`` under the same keys, ``layer_types``
    whole; NOTHING is cut: ``reduced`` is empty."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == NAME)
        assert row["config"] == published
        assert row["source_url"] == cfg["source"]
    assert {k for k, v in published.items()
            if cfg.get(k, "absent") != v} == set() == set(cfg["reduced"])
    assert {"sandwich_norms", "final_norm_in_the_loop", "exit_rule",
            "biases", "weights", "sandwich_output_norms", "sampling"} \
        <= set(cfg["assumed"])
    assert "overrides" not in cfg        # tunables at the program's defaults
    entry = bench["configs"][4]          # the fifth; later PRs append
    assert (entry["name"], entry["reduced"], entry["file"]) \
        == (NAME, [], "benchmark/configs/Ouro-2.6B.json")
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    cell = bench["workloads"][4]
    assert (cell["name"], cell["config"], cell["chips"]) == (CELL, NAME, 1)
    assert len(cell["why"]) <= 200
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    # 0.22 streams retire a tick, so the 95th percentile gap sits on the
    # one-refill tick for every seed (0.99% between quartiles over 6 seeds:
    # PERF.md section 6): the cell reports both tails
    assert {"out_tokens_per_s", "gap_ms_p95", "tick_ms_p95.serve",
            "setup_s", "slot_occupancy",
            "decode_step_ms", "device_idle_pct.serve", "hbm_peak_gib.serve",
            "tick_sched_ms.serve", "tick_dispatch_ms.serve",
            "tick_device_wait_ms.serve", "tick_harvest_ms.serve",
            "looped_decode_step_roofline", "ut_decode_kernel_roofline",
            "ut_expected_exit_step"} <= reports
    # the plain step roofline counts every weight once: a quarter of the
    # truth here, so the cell reports its own
    assert not {"decode_step_roofline", "prefix_hit_share",
                "moe_experts_hit_share"} & reports
    # the three this cell brought, in the order they were appended (later
    # PRs append after them)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "looped_decode_step_roofline", "ut_decode_kernel_roofline",
        "ut_expected_exit_step"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
               for m in new)
    assert [(m["unit"], m["source"], m["layer"], m["better"]) for m in new] \
        == [("%", "device_trace", "kernels", "higher"),
            ("%", "device_trace", "kernels", "higher"),
            ("passes", "program_counter", "model step", "lower")]
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 64 * 1024


def test_configuration_builds_the_class_it_names(cfg):
    from benchmark import weights_by_class
    c = weights_by_class.model_config(cfg)
    assert (c.total_ut_steps, c.early_exit_threshold, c.num_hidden_layers,
            c.num_key_value_heads, c.head_dim, c.sliding_window, c.dtype) \
        == (4, 1, 48, 16, 128, None, "bfloat16")
    assert (c.rope_theta, c.rms_norm_eps, c.max_position_embeddings) \
        == (1000000, 1e-06, 65536)
    assert c.model_class.endswith(":OuroForCausalLM")
    # the two output norms of a layer are drawn small (the file says why)
    assert c.init_overrides == {"layernorm_2.weight": {"normal_std": 0.1}}


def test_cell_file_is_the_issues_traffic():
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    t = cell["traffic"]
    assert (cell["config"], cell["kind"], cell["reference"],
            cell["generator"]) == (NAME, "serve_looped", "ouro", "general")
    assert t["arrivals"] == {"process": "backlog", "depth": 4}
    assert t["prompt_len"] == {"dist": "uniform", "lo": 64, "hi": 192}
    assert t["output_len"] == {"dist": "uniform", "lo": 128, "hi": 448}
    assert t["first_wave_output_len"] == {"dist": "uniform", "lo": 1,
                                          "hi": 448}
    assert "shared_prefix" not in t
    assert (t["first_wave"], t["pool"], t["shape_seed"]) == (8, 128, 1)
    assert (cell["warm_s"], cell["drain_s"], cell["trace_s"]) == (30, 0, 4)
    assert "FOUR times" in cell["why"] and "one 16 GB accelerator" \
        in cell["who"]
