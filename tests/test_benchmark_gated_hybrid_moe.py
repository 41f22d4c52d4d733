"""The benchmark's arithmetic for the cell with window and full attention
layers of their own query heads, a per-head gate, a shared expert and a
share of the routed experts (``laguna-repo-agent-decode``): sizes from
shapes, the required bytes of a step and of each read, the readers, and
the configuration file held to the catalog row it was taken from."""
import argparse
import json
import os

import pytest

from benchmark import common
from benchmark import flops_gated_hybrid_moe as f

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "Laguna-S-2.1"
CELL = "laguna-repo-agent-decode"
NEW_METRICS = ["gated_moe_decode_step_roofline", "moe_experts_decode_roofline",
               "gated_swa_decode_kernel_roofline",
               "gated_full_decode_kernel_roofline"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_sizes_from_shapes(cfg):
    """The issue's table: attention 44,187,648 (full) and 63,135,744
    (sliding), an expert 9,437,184, the dense layer 157,440,000, a sliding
    expert layer 1,281,325,056 and the full one 1,262,376,960 (each + 256
    for the router's zero selection bias, which the model holds), embedding
    + head 308,281,344: 5,572,077,568 in all = 11.14 GB."""
    sizes = cfg["sizes"]
    assert f.layer_kinds(cfg) == [(0, 0), (1, 1), (1, 1), (1, 1), (0, 1)]
    assert f.attention_params(cfg, 0) == 2 * 18874368 + 2 * 3145728 \
        + 147456 == 44187648 \
        == sizes["attention_parameters_full_layer"]
    assert f.attention_params(cfg, 1) == 2 * 28311552 + 2 * 3145728 \
        + 221184 == 63135744 == sizes["attention_parameters_sliding_layer"]
    assert f.expert_params(cfg) == f.shared_params(cfg) == 9437184
    assert (f.router_width(cfg), f.experts_held(cfg)) == (256, 128)
    assert f.layer_fixed_params(cfg, 0) == 157440000 \
        == sizes["dense_layer_parameters"]
    assert f.layer_fixed_params(cfg, 1) + 128 * f.expert_params(cfg) \
        == 1281325056 + 256 == sizes["sliding_expert_layer_parameters"]
    assert f.layer_fixed_params(cfg, 4) + 128 * f.expert_params(cfg) \
        == 1262376960 + 256 == sizes["full_expert_layer_parameters"]
    assert f.total_params(cfg) == 5572076544 + 4 * 256 \
        == sizes["parameters_total"]
    assert round(f.total_params(cfg) * 2 / 1e9, 2) \
        == sizes["weights_gb_bf16"] == 11.14
    assert f.kv_bytes_per_token(cfg) == 4096 \
        == sizes["kv_bytes_per_token_per_layer"]
    from paddle_tpu.models.laguna import LagunaConfig
    assert 8 * LagunaConfig().kv_row * 2 == 4096


def test_model_holds_what_the_arithmetic_counts(cfg):
    """The class built on abstract weights at the served cut holds exactly
    ``total_params`` parameters."""
    from benchmark import weights_by_class
    from paddle_tpu.models.laguna import LagunaForCausalLM
    from paddle_tpu.utils.scale import abstract_init
    c = weights_by_class.model_config(cfg, num_experts=256,
                                      experts_held=(0, 128))
    with abstract_init("bfloat16"):
        model = LagunaForCausalLM(c)
    assert model.num_params() == f.total_params(cfg)
    assert (c.num_experts, c.experts_held, c.num_experts_per_tok,
            c.num_hidden_layers, c.dtype, c.scoring_func) \
        == (256, (0, 128), 10, 5, "bfloat16", "softmax")
    assert c.hybrid_layer_pattern == (0, 1, 1, 1, 0)
    assert c.model_class.endswith(":LagunaForCausalLM")


def test_decode_step_bytes_follow_the_routing_and_the_window(cfg):
    none = f.decode_step_bytes(cfg, 0, 0, 0)
    assert none == 2 * f.fixed_params(cfg)
    assert f.decode_step_bytes(cfg, 0, 0, 1) - none == 2 * 9437184 \
        == f.expert_bytes(cfg, 1)
    # a live row costs 4,096 B in each of the 2 full layers; a row inside
    # the window 4,096 B in each of the 3 sliding layers
    assert f.decode_step_bytes(cfg, 1000, 0, 0) - none == 1000 * 4096 * 2
    assert f.decode_step_bytes(cfg, 0, 1000, 0) - none == 1000 * 4096 * 3
    # the issue's reckoning: 64 slots at 9.1k, 512 rows in the window,
    # every expert held hit in 4 layers: about 16.0 GB, 19.5 ms at 819 GB/s
    full = f.decode_step_bytes(cfg, 64 * 9100, 64 * 512, 4 * 128)
    assert 15.9e9 < full < 16.1e9
    assert 9.6e9 < f.expert_bytes(cfg, 4 * 128) < 9.7e9
    assert (f.layers_of(cfg, f.FULL), f.layers_of(cfg, f.WINDOW)) == (2, 3)


def test_readers_divide_by_their_own_layers(cfg):
    """64 slots at 9.1k: the full read's 2.39 GB a call in 4 ms, the
    window read's 134 MB in 0.25 ms, the experts' 8.9 GB a step in 13 ms;
    the readers decline, never raise, without the counters or the trace."""
    peaks = {"hbm_bytes_per_s": 819e9}
    window = {"decode_module": "jit_block_fn", "decode_block": 8,
              "kv_rows_per_step": 64 * 9100,
              "window_kv_rows_per_step": 64 * 512,
              "moe_expert_hits_per_step": 470.0}
    ctx = argparse.Namespace(
        window=window, peaks=peaks, config=cfg,
        trace_summary={"modules": {"jit_block_fn": (10, 10 * 8 * 0.03)}},
        kernel_seconds={"paged_attention_decode": (160, 80 * 2 * 4e-3),
                        "swa_paged_attention_decode": (240, 80 * 3 * 2.5e-4)},
        scope_seconds={"moe_experts": (400, 80 * 0.013)})

    def read(name):
        return common.load_module("layer_metrics", name + ".py").read(ctx)
    assert read("gated_full_decode_kernel_roofline") == pytest.approx(
        64 * 9100 * 4096 / 819e9 / 4e-3 * 100)
    assert read("gated_swa_decode_kernel_roofline") == pytest.approx(
        64 * 512 * 4096 / 819e9 / 2.5e-4 * 100)
    assert read("moe_experts_decode_roofline") == pytest.approx(
        470 * 2 * 9437184 / 819e9 / 0.013 * 100)
    assert read("gated_moe_decode_step_roofline") == pytest.approx(
        f.decode_step_bytes(cfg, 64 * 9100, 64 * 512, 470.0) / 819e9
        / 0.03 * 100)
    assert all(0 < read(n) < 100 for n in NEW_METRICS)
    empty = argparse.Namespace(window={}, trace_summary={}, peaks=None,
                               config={}, kernel_seconds=None,
                               scope_seconds=None)
    ctx = empty
    assert all(read(n) is None for n in NEW_METRICS)
    # a MiMo cell's configuration has no per-layer heads: declined too
    ctx = argparse.Namespace(**dict(vars(empty), window=window, peaks=peaks,
                                    config={"num_hidden_layers": 7}))
    assert all(read(n) is None for n in NEW_METRICS)


def test_configuration_file_keeps_every_published_key(cfg, bench):
    """The catalog row's ``config`` under the same keys, the per-layer lists
    and both rope groups whole; only the depth, the experts held and the
    vocabulary are cut, and the file says how."""
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [0], "tie_word_embeddings": False,
        "gating": "per-head", "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 12,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12}
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) \
        == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_experts_published"], cfg["experts_held"],
            cfg["vocab_size"], cfg["vocab_size_published"]) \
        == (5, 128, 256, [0, 128], 50176, 100352)
    assert cfg["scoring_func"] == "softmax"
    assert {"scoring_func", "shared_expert_gate", "attention_gate",
            "sliding_window", "qk_norm", "rope_pairs"} <= set(cfg["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry == bench["configs"][6] and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"]
    # the one override the issue allows: kanana's, with kanana's reason
    assert {k: v["value"] for k, v in cfg["overrides"].items()} \
        == {"prefill_chunk": 512}
    # both pools and the weights, as a deployment would hold them
    dep = cfg["deployment"]
    pools = dep["num_blocks"] * 16 * 4096 * 2 \
        + dep["window_blocks"] * 16 * 4096 * 3
    assert 13.9e9 < pools + 2 * cfg["sizes"]["parameters_total"] < 14.1e9
    assert dep["window_blocks"] >= 1 + dep["num_slots"] * (65 + 32)
    assert (dep["num_slots"], dep["max_len"]) == (64, 10240)
    assert dep["check_context"] > 8192


def test_the_cell_and_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["config"] == NAME)
    assert (cell["name"], cell["chips"], cell["traffic"]) \
        == (CELL, 1, "repo-agent-decode")
    assert cell == bench["workloads"][6] and len(cell["why"]) <= 200
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"out_tokens_per_s", "setup_s", "decode_step_ms", "slot_occupancy",
            "prefix_hit_share", "swa_kv_resident_share",
            "moe_experts_hit_share", "hbm_peak_gib.serve",
            "device_idle_pct.serve", "tick_sched_ms.serve",
            "tick_dispatch_ms.serve", "tick_device_wait_ms.serve",
            "tick_harvest_ms.serve", "device_starved_ms.serve",
            "sync_tail_ms.serve", "admit_ms_per_request.serve",
            "chunk_dispatch_ms_per_chunk.serve",
            "between_ticks_ms.serve", "gap_ms_p95",
            "tick_ms_p95.serve"} | set(NEW_METRICS) <= reports
    # MiMo's readers count MiMo's keys; the step's plain roofline counts
    # a dense model
    assert not {"decode_step_roofline", "swa_decode_kernel_roofline",
                "full_decode_kernel_roofline", "swa_moe_decode_step_roofline",
                "train_tokens_per_s"} & reports
    ours = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in ours] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
               and (m["unit"], m["source"], m["layer"], m["better"])
               == ("%", "device_trace", "kernels", "higher") for m in ours)
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 64 * 1024


def test_cell_file_is_the_issues_traffic():
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as fh:
        cell = json.load(fh)
    t = cell["traffic"]
    assert (cell["kind"], cell["reference"], cell["generator"]) \
        == ("serve_gated_hybrid_moe", "laguna", "general")
    assert t["arrivals"] == {"process": "backlog", "depth": 8}
    assert t["shared_prefix"] == {"share": 1.0, "len": 8192, "count": 4}
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (8256, 8704)
    assert (t["output_len"]["lo"], t["output_len"]["hi"]) == (384, 1152)
    assert (t["first_wave_output_len"]["lo"],
            t["first_wave_output_len"]["hi"]) == (1, 1152)
    assert (t["first_wave"], t["pool"], t["shape_seed"]) == (64, 128, 1)
    assert (cell["warm_s"], cell["drain_s"], cell["trace_s"]) == (30, 0, 4)
