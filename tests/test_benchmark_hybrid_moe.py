"""The benchmark's arithmetic for the cell with window and full attention
layers and a share of the routed experts: sizes from shapes, both kernels'
device time summed apart, and the configuration file held to the catalog
row it was taken from."""
import json
import os

import pytest

from benchmark import flops_hybrid_moe as fhm
from benchmark import trace_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "MiMo-V2-Flash"
CELL = "mimo-v2-agent-decode"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


def test_sizes_from_shapes(cfg):
    """The issue's arithmetic, from the file: attention 89.13M (full) and
    94.37M (window), an expert 25.17M, the dense layer 290.46M, a window
    expert layer 498.07M, the full one 492.83M, embedding + head 1,249.9M,
    4,523.6M in all = 9.05 GB."""
    sizes = cfg["sizes"]
    assert fhm.layer_kinds(cfg) == [(0, 0), (1, 1), (1, 1), (1, 1), (1, 1),
                                    (0, 1), (1, 1)]
    assert fhm.attention_params(cfg, fhm.FULL) == 4096 * 64 * 192 \
        + 4096 * 4 * 320 + 8192 * 4096 \
        == sizes["attention_parameters_full_layer"]
    assert fhm.attention_params(cfg, fhm.WINDOW) == 4096 * 64 * 192 \
        + 4096 * 8 * 320 + 8192 * 4096 + 64 \
        == sizes["attention_parameters_window_layer"]
    assert fhm.expert_params(cfg) == 3 * 4096 * 2048 \
        == sizes["one_routed_expert_parameters"]
    assert (fhm.router_width(cfg), fhm.experts_held(cfg)) == (256, 16)
    assert fhm.layer_fixed_params(cfg, 0, 0) \
        == sizes["dense_layer_parameters"] == 290463744
    assert fhm.layer_fixed_params(cfg, 1, 1) + 16 * fhm.expert_params(cfg) \
        == sizes["window_expert_layer_parameters"]
    assert fhm.layer_fixed_params(cfg, 0, 1) + 16 * fhm.expert_params(cfg) \
        == sizes["full_expert_layer_parameters"]
    assert fhm.total_params(cfg) == sizes["parameters_total"] == 4523620160
    assert round(fhm.total_params(cfg) * 2 / 1e9, 2) \
        == sizes["weights_gb_bf16"] == 9.05
    assert fhm.kv_bytes_per_token(cfg, fhm.FULL) == 2560 \
        == sizes["kv_bytes_per_token_full_layer_required"]
    assert fhm.kv_bytes_per_token(cfg, fhm.WINDOW) == 5120 \
        == sizes["kv_bytes_per_token_window_layer_required"]
    from paddle_tpu.models.mimo_v2 import MiMoV2Config
    row = MiMoV2Config().kv_row
    assert (4 * row * 2, 8 * row * 2) == (3072, 6144) == (
        sizes["kv_bytes_per_token_full_layer_stored"],
        sizes["kv_bytes_per_token_window_layer_stored"])


def test_decode_step_bytes_follow_the_routing_and_the_window(cfg):
    none = fhm.decode_step_bytes(cfg, 0, 0, 0)
    assert none == 2 * fhm.fixed_params(cfg) == 2 * (
        290463744 + 5 * 95428928 + 90185984 + 4096 * 152576 + 4096)
    assert fhm.decode_step_bytes(cfg, 0, 0, 1) - none == 2 * 25165824
    # a live row costs 2,560 B in each of the 2 full layers; a row inside
    # the window 5,120 B in each of the 5 window layers
    assert fhm.decode_step_bytes(cfg, 1000, 0, 0) - none == 1000 * 2560 * 2
    assert fhm.decode_step_bytes(cfg, 0, 1000, 0) - none == 1000 * 5120 * 5
    # the issue's reckoning: 64 slots at 7.0k, 128 rows in the window,
    # about 13 of 16 experts in 6 layers: 9.4 GB, 11.4 ms at 819 GB/s
    full = fhm.decode_step_bytes(cfg, 64 * 7000, 64 * 128, 6 * 13)
    assert 9.3e9 < full < 9.5e9
    assert fhm.full_decode_kernel_bytes(cfg, 64 * 7000) == 64 * 7000 * 2560
    assert fhm.swa_decode_kernel_bytes(cfg, 64 * 128) == 64 * 128 * 5120
    assert (fhm.layers_of(cfg, fhm.FULL), fhm.layers_of(cfg, fhm.WINDOW)) \
        == (2, 5)
    assert fhm.decode_step_flops(cfg, 64, 64 * 7000, 64 * 128) > 0


def test_the_two_kernels_seconds_are_summed_apart():
    """``swa_paged_attention_decode`` does not start with
    ``paged_attention_decode``: a prefix sums one kernel's sites only."""
    ops = [("%swa_paged_attention_decode.3 = bf16[64,64,128] custom-call(q)",
            0, 100_000),
           ("%paged_attention_decode.1 = bf16[64,64,128] custom-call(q)",
            100_000, 2_000_000),
           ("%swa_paged_attention_decode.9 = bf16[64,64,128] custom-call(q)",
            2_100_000, 100_000), ("%fusion.7 = f32[] fusion()", 0, 5)]
    loaded = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}}}
    out = trace_kernels.seconds_by_prefix(
        loaded, ("paged_attention_decode", "swa_paged_attention_decode"))
    assert out["paged_attention_decode"] == (1, pytest.approx(2e-3))
    assert out["swa_paged_attention_decode"] == (2, pytest.approx(2e-4))


def test_readers_divide_by_their_own_layers(cfg):
    """64 slots at 7k: the full read's 1.15 GB a call in 2 ms is 70% of
    the roofline; the window read's 42 MB in 0.1 ms is 51%; the readers
    decline, never raise, where the program has no such counters."""
    import argparse
    from benchmark import common
    peaks = {"hbm_bytes_per_s": 819e9}
    window = {"decode_module": "jit_block_fn", "decode_block": 8,
              "kv_rows_per_step": 64 * 7000,
              "window_kv_rows_per_step": 64 * 128,
              "moe_expert_hits_per_step": 78.0,
              "swa_kv_resident_share": 0.1}
    ctx = argparse.Namespace(
        window=window, peaks=peaks, config=cfg,
        trace_summary={"modules": {"jit_block_fn": (10, 10 * 8 * 0.016)}},
        kernel_seconds={"paged_attention_decode": (160, 80 * 2 * 2e-3),
                        "swa_paged_attention_decode": (400, 80 * 5 * 1e-4)})
    read = lambda name: common.load_module(           # noqa: E731
        "layer_metrics", name + ".py").read(ctx)
    assert read("full_decode_kernel_roofline") == pytest.approx(
        64 * 7000 * 2560 / 819e9 / 2e-3 * 100)
    assert read("swa_decode_kernel_roofline") == pytest.approx(
        64 * 128 * 5120 / 819e9 / 1e-4 * 100)
    assert read("swa_moe_decode_step_roofline") == pytest.approx(
        fhm.decode_step_bytes(cfg, 64 * 7000, 64 * 128, 78.0) / 819e9
        / 0.016 * 100)
    assert read("swa_kv_resident_share") == 0.1
    assert all(v < 100 for v in map(read, (
        "full_decode_kernel_roofline", "swa_decode_kernel_roofline",
        "swa_moe_decode_step_roofline")))
    ctx = argparse.Namespace(window={}, trace_summary={}, peaks=None,
                             config={}, kernel_seconds=None)
    for name in ("swa_moe_decode_step_roofline", "swa_decode_kernel_roofline",
                 "full_decode_kernel_roofline", "swa_kv_resident_share"):
        assert read(name) is None


def test_configuration_file_keeps_every_published_key(cfg):
    """The catalog row's ``config`` under the same keys, the two per-layer
    lists whole; only the depth and the experts held are cut, and the file
    says how."""
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    published = {
        "attention_value_scale": 0.707, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384,
        "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
        "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
        "rope_theta": 5000000, "tie_word_embeddings": False,
        "vocab_size": 152576, "partial_rotary_factor": 0.334,
        "sliding_window": 128, "swa_rope_theta": 10000,
        "attention_bias": False, "v_head_dim": 128,
        "hybrid_layer_pattern": pattern,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "sliding_window_size": 128,
        "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": None, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 8, "swa_head_dim": 192,
        "swa_v_head_dim": 128}
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) \
        == {"num_hidden_layers", "n_routed_experts"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["n_routed_experts_published"], cfg["experts_held"]) \
        == (7, 16, 256, [0, 16])
    assert {"multi_token_prediction", "attention_value_scale",
            "attention_sink_bias", "e_score_correction_bias"} \
        <= set(cfg["assumed"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert entry == bench["configs"][3] and len(entry["why"]) <= 200
    cell = next(w for w in bench["workloads"] if w["config"] == NAME)
    assert (cell["name"], cell["chips"]) == (CELL, 1)
    assert cell == bench["workloads"][3] and len(cell["why"]) <= 200
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"out_tokens_per_s", "setup_s", "decode_step_ms",
            "swa_moe_decode_step_roofline", "swa_decode_kernel_roofline",
            "full_decode_kernel_roofline", "swa_kv_resident_share",
            "prefix_hit_share", "slot_occupancy",
            "hbm_peak_gib.serve", "device_idle_pct.serve"} <= reports
    # a backlog cell's end-to-end metric is the tokens a second: its 95th
    # percentile gap falls on the tick with two refills or on the one with
    # three by the seed (PERF.md section 6), and with every held expert
    # read every step the experts hit no longer move the step
    assert not {"decode_step_roofline", "moe_mla_decode_step_roofline",
                "mla_decode_kernel_roofline", "gap_ms_p95",
                "tick_ms_p95.serve", "moe_experts_hit_share"} & reports
    assert [m["name"] for m in bench["per_layer"][21:25]] == [
        "swa_moe_decode_step_roofline", "swa_decode_kernel_roofline",
        "full_decode_kernel_roofline", "swa_kv_resident_share"]
    # the one override the issue allows: kanana's, with kanana's reason
    assert {k: v["value"] for k, v in cfg["overrides"].items()} \
        == {"prefill_chunk": 512}
    assert all(len(v["why"]) > 100 for v in cfg["overrides"].values())
    # both pools and the weights, as a deployment would hold them
    dep = cfg["deployment"]
    pools = dep["num_blocks"] * 16 * 3072 * 2 \
        + dep["window_blocks"] * 16 * 6144 * 5
    assert 0.25 * 16e9 < pools + 2 * cfg["sizes"]["parameters_total"] < 14e9
    assert dep["window_blocks"] >= 1 + dep["num_slots"] * (41 + 8)


def test_configuration_builds_the_class_it_names(cfg):
    from benchmark import weights_by_class
    c = weights_by_class.model_config(
        cfg, n_routed_experts=fhm.router_width(cfg),
        experts_held=tuple(cfg["experts_held"]))
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok,
            c.num_hidden_layers, c.dtype) == (256, (0, 16), 8, 7, "bfloat16")
    assert c.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert c.moe_layer_freq == (0, 1, 1, 1, 1, 1, 1)
    assert c.model_class.endswith(":MiMoV2ForCausalLM")
    assert set(c.init_overrides) == {"e_score_correction_bias",
                                     "attention_sink_bias"}


def test_cell_file_is_the_issues_traffic():
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    t = cell["traffic"]
    assert (cell["kind"], cell["reference"], cell["generator"]) \
        == ("serve_hybrid_moe", "mimo_v2", "general")
    assert t["arrivals"] == {"process": "backlog", "depth": 8}
    assert t["shared_prefix"] == {"share": 1.0, "len": 6144, "count": 4}
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (6208, 6656)
    assert (t["output_len"]["lo"], t["output_len"]["hi"]) == (256, 1024)
    assert (t["first_wave"], t["pool"], t["shape_seed"]) == (64, 128, 1)
    assert (cell["warm_s"], cell["drain_s"], cell["trace_s"]) == (30, 0, 4)
