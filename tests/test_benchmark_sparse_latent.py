"""The benchmark's arithmetic for the cell with learned sparse attention
over a latent cache: sizes from shapes, required bytes of each read, the
scopes' device time, and the configuration file held to the catalog row it
was taken from."""
import argparse
import json
import os

import pytest

from benchmark import common
from benchmark import flops_sparse_latent_moe as f
from benchmark import trace_kernels, trace_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "dots3-note-prev"
CELL = "dots3-longdoc-decode"
NEW_METRICS = ["dsa_moe_decode_step_roofline", "dsa_index_kernel_roofline",
               "dsa_sparse_read_roofline", "swa_mla_decode_kernel_roofline",
               "dsa_select_ms_per_step", "dsa_selected_share"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        return json.load(fh)


def test_sizes_from_shapes(cfg):
    """The issue's arithmetic, from the file: full attention 144.05M
    (indexer 9.37M of it), sliding 90.83M, an expert 23.59M, layer 0
    356.40M, the full expert layer 546.45M, a sliding one 493.24M,
    embedding + head 1,557.1M, 3,939.7M in all = 7.88 GB."""
    sizes = cfg["sizes"]
    assert f.layer_kinds(cfg) == [(0, 0), (0, 1), (1, 1), (1, 1), (1, 1)]
    assert f.attention_params(cfg, f.FULL) == 5242880 + 25165824 + 2949120 \
        + 16777216 + 83886080 + 655360 + 1536 \
        + (8388608 + 655360 + 327680 + 256) == 144049920 \
        == sizes["attention_parameters_full_layer"]
    assert f.attention_params(cfg, f.WINDOW) == 5242880 + 16777216 \
        + 5570560 + 20971520 + 41943040 + 327680 + 2048 == 90834944 \
        == sizes["attention_parameters_sliding_layer"]
    assert f.expert_params(cfg) == 3 * 5120 * 1536 == 23592960 \
        == sizes["one_routed_expert_parameters"]
    assert (f.router_width(cfg), f.experts_held(cfg)) == (256, 16)
    assert f.layer_params(cfg, 0, 0) == 356396800 \
        == sizes["layer_0_parameters"]
    assert f.layer_params(cfg, 0, 1) == 168964096 + 16 * 23592960 \
        == 546451456 == sizes["full_expert_layer_parameters"]
    assert f.layer_params(cfg, 1, 1) == 115749120 + 377487360 == 493236480 \
        == sizes["sliding_expert_layer_parameters"]
    assert f.total_params(cfg) == 3939698176 == sizes["parameters_total"]
    assert round(f.total_params(cfg) * 2 / 1e9, 2) == 7.88 \
        == sizes["weights_gb_bf16"]
    assert f.step_weight_params(cfg) * 2 == 6322260992 \
        == sizes["decode_step_weight_bytes"]
    assert f.latent_bytes_per_token(cfg, f.FULL) + f.index_key_bytes(cfg) \
        == 1408 == sizes["cache_bytes_per_token_full_layer_required"]
    assert f.latent_bytes_per_token(cfg, f.WINDOW) == 2176 \
        == sizes["cache_bytes_per_token_sliding_layer_required"]
    from paddle_tpu.models.dots3_note import Dots3NoteConfig
    c = Dots3NoteConfig()
    assert ((c.latent_row(0) + c.index_head_dim) * 2,
            c.latent_row(1) * 2) == (1536, 2304) == (
        sizes["cache_bytes_per_token_full_layer_stored"],
        sizes["cache_bytes_per_token_sliding_layer_stored"])


def test_the_model_holds_the_parameters_the_arithmetic_counts(cfg):
    """The class at the published widths, built abstractly: the same
    count, so no matrix of the model is missing from the bytes."""
    from benchmark import weights_by_class
    from paddle_tpu.models.dots3_note import Dots3NoteForCausalLM
    from paddle_tpu.utils.scale import abstract_init
    c = weights_by_class.model_config(
        cfg, n_routed_experts=256, experts_held=(0, 16))
    with abstract_init("bfloat16"):
        model = Dots3NoteForCausalLM(c)
    assert model.num_params() == f.total_params(cfg)


def test_decode_step_bytes_follow_the_selection_and_the_window(cfg):
    none = f.decode_step_bytes(cfg, 0, 0)
    assert none == 2 * f.step_weight_params(cfg)
    # a live token costs its 256 B index key in each of the 2 full layers;
    # a selected row 1,152 B in each; a row inside the window 2,176 B in
    # each of the 3 sliding layers
    assert f.decode_step_bytes(cfg, 1000, 1, 0, 0) - none == 1000 * 256 * 2
    assert f.decode_step_bytes(cfg, 0, 1, 1000, 0) - none == 1000 * 1152 * 2
    assert f.decode_step_bytes(cfg, 0, 1, 0, 1000) - none == 1000 * 2176 * 3
    # the defaults: slots x min(mean context, index_topk | window)
    assert f.decode_step_bytes(cfg, 64 * 33500, 64) == f.decode_step_bytes(
        cfg, 64 * 33500, 64, 64 * 2048, 64 * 513)
    assert f.decode_step_bytes(cfg, 64 * 100, 64) == f.decode_step_bytes(
        cfg, 64 * 100, 64, 64 * 100, 64 * 100)
    # the issue's reckoning: 6.32 GB of weights + 1.10 GB of keys + 0.30 +
    # 0.21 GB of rows = 7.94 GB, 9.7 ms at 819 GB/s
    full = f.decode_step_bytes(cfg, 64 * 33500, 64)
    assert 7.9e9 < full < 8.0e9 and 9.6 < full / 819e9 * 1e3 < 9.8
    assert f.index_kernel_bytes(cfg, 64 * 33500) == 64 * 33500 * 256
    assert f.sparse_read_bytes(cfg, 64 * 2048) == 64 * 2048 * 1152
    assert f.window_read_bytes(cfg, 64 * 513) == 64 * 513 * 2176
    assert (f.layers_of(cfg, f.FULL), f.layers_of(cfg, f.WINDOW)) == (2, 3)


def test_kernels_and_scopes_seconds_are_summed_apart():
    """``swa_mla_paged_attention_decode`` starts with neither
    ``mla_paged_attention_decode`` nor ``swa_paged_attention_decode``; an
    operation belongs to the scope that its instruction's op_name, in the
    compiled program's text, names as a path component; only operations
    inside that program's executions count, and nested ones once."""
    ops = [("%swa_mla_paged_attention_decode.3 = bf16[] custom-call(q)", 0,
            100_000),
           ("%mla_paged_attention_decode.1 = bf16[] custom-call(q)", 100_000,
            2_000_000),
           ("%dsa_index_scores_decode.9 = f32[] custom-call(q)", 2_100_000,
            300_000)]
    loaded = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}}}
    out = trace_kernels.seconds_by_prefix(
        loaded, ("mla_paged_attention_decode", "swa_paged_attention_decode",
                 "swa_mla_paged_attention_decode", "dsa_index_scores_decode"))
    assert out["swa_mla_paged_attention_decode"] == (1, pytest.approx(1e-4))
    assert out["mla_paged_attention_decode"] == (1, pytest.approx(2e-3))
    assert out["swa_paged_attention_decode"] == (0, 0.0)
    assert out["dsa_index_scores_decode"] == (1, pytest.approx(3e-4))

    text = """
HloModule jit_block_fn
%region_57 (a: f32[], b: f32[]) -> pred[] {
  ROOT %compare.1 = pred[] compare(%a, %b), direction=GT
}
  %sort.42 = (f32[64,1,36864]{2,0,1}, s32[64,1,36864]{2,0,1}) sort(%fusion.975, %iota.566), dimensions={2}, is_stable=true, to_apply=%region_57, metadata={op_name="jit(block_fn)/while/body/closed_call/attn/dsa_select/top_k" source_file="x.py" source_line=1}
  %fusion.9 = s32[64,1,2048]{2,0,1} fusion(%sort.42), kind=kLoop, calls=%f, metadata={op_name="jit(block_fn)/while/body/closed_call/attn/dsa_select/min"}
  %fusion.1079 = bf16[64,2048,640]{2,1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(block_fn)/while/body/closed_call/attn/dsa_read/gather"}
  %fusion.1 = f32[64]{0} fusion(%p), kind=kLoop, calls=%h, metadata={op_name="jit(block_fn)/while/body/closed_call/attn/not_dsa_read/x"}
  %dsa_sparse_mla_decode.5 = bf16[64,128,512]{2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(block_fn)/while/body/closed_call/attn/dsa_read/jit(_sparse_pallas_call)/pallas_call"}
"""
    assert trace_scopes.scopes_of_program(text, ("dsa_select", "dsa_read")) \
        == {"sort.42": "dsa_select", "fusion.9": "dsa_select",
            "fusion.1079": "dsa_read", "dsa_sparse_mla_decode.5": "dsa_read"}
    ops = [("%sort.42 = (f32[64,1,36864]) sort(...)", 0, 1_000_000),
           ("%fusion.9 = s32[] fusion()", 100, 200_000),   # inside the sort
           ("%fusion.1079 = bf16[] fusion()", 2_000_000, 500_000),
           ("%fusion.1 = f32[] fusion()", 3_000_000, 500_000),
           ("%dsa_sparse_mla_decode.5 = bf16[] custom-call()", 4_000_000,
            250_000),
           # the chunk program's own sort.42, outside the block's runs
           ("%sort.42 = (f32[512,36864]) sort(...)", 6_000_000, 9_000_000)]
    modules = [("jit_block_fn(123)", 0, 5_000_000),
               ("jit_chunk_fn(456)", 5_500_000, 10_000_000)]
    loaded = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}
    got = trace_scopes.seconds_by_scope(loaded, "jit_block_fn", text,
                                        ("dsa_select", "dsa_read",
                                         "dsa_index"))
    assert got["dsa_select"] == (2, pytest.approx(1e-3))
    assert got["dsa_read"] == (2, pytest.approx(7.5e-4))
    assert "dsa_index" not in got


def test_readers_divide_by_their_own_layers_and_decline_without_counters(cfg):
    """64 slots at 33.5k: the indexer's 549 MB a call in 1 ms is 67% of the
    roofline; the selected read's 151 MB under ``dsa_read`` in 0.8 ms 23%;
    the window read's 71 MB in 0.15 ms 58%; the selection 6 ms a step. The
    readers decline, never raise, where the program has no such counters."""
    peaks = {"hbm_bytes_per_s": 819e9}
    window = {"decode_module": "jit_block_fn", "decode_block": 8,
              "slots": 64.0, "kv_rows_per_step": 64 * 33500.0,
              "window_kv_rows_per_step": 64 * 513.0,
              "dsa_selected_rows_per_step": 64 * 2048.0,
              "dsa_selected_share": 2048 / 33500}
    ctx = argparse.Namespace(
        window=window, peaks=peaks, config=cfg,
        trace_summary={"modules": {"jit_block_fn": (10, 10 * 8 * 0.020)}},
        kernel_seconds={
            "dsa_index_scores_decode": (160, 80 * 2 * 1e-3),
            "dsa_sparse_mla_decode": (160, 80 * 2 * 2e-4),
            "swa_mla_paged_attention_decode": (240, 80 * 3 * 1.5e-4)},
        scope_seconds={"dsa_read": (320, 80 * 2 * 8e-4),
                       "dsa_select": (160, 80 * 6e-3)})
    read = lambda name: common.load_module(           # noqa: E731
        "layer_metrics", name + ".py").read(ctx)
    assert read("dsa_index_kernel_roofline") == pytest.approx(
        64 * 33500 * 256 / 819e9 / 1e-3 * 100)
    assert read("dsa_sparse_read_roofline") == pytest.approx(
        64 * 2048 * 1152 / 819e9 / 8e-4 * 100)
    assert read("swa_mla_decode_kernel_roofline") == pytest.approx(
        64 * 513 * 2176 / 819e9 / 1.5e-4 * 100)
    assert read("dsa_select_ms_per_step") == pytest.approx(6.0)
    assert read("dsa_moe_decode_step_roofline") == pytest.approx(
        f.decode_step_bytes(cfg, 64 * 33500, 64) / 819e9 / 0.020 * 100)
    assert read("dsa_selected_share") == pytest.approx(2048 / 33500)
    assert all(read(n) < 100 for n in NEW_METRICS[:4])
    ctx = argparse.Namespace(window={}, trace_summary={}, peaks=None,
                             config={}, kernel_seconds=None,
                             scope_seconds=None)
    assert all(read(n) is None for n in NEW_METRICS)


def test_configuration_file_keeps_every_published_key(cfg):
    """The catalog row's ``config`` under the same keys, ``layer_types``
    whole; only the depth and the experts held are cut, and the file says
    how."""
    types = ["full_attention"] + ["full_attention", "sliding_attention",
                                  "sliding_attention",
                                  "sliding_attention"] * 12
    published = {
        "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
        "attention_gate_type": "headwise", "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
        "kv_lora_rank": 512, "layer_types": types[:46],
        "max_position_embeddings": 524288, "model_type": "dots3_note",
        "moe_intermediate_size": 1536, "moe_layer_freq": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 46,
        "num_key_value_heads": 128, "q_lora_rank": 1024,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
        "routed_scaling_factor": 1, "scoring_func": "sigmoid",
        "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
        "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
        "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
        "swa_rope_theta": 50000, "swa_v_head_dim": 128,
        "tie_word_embeddings": False, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 152064}
    assert len(published["layer_types"]) == 46
    assert published["layer_types"].count("full_attention") == 13
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) \
        == {"num_hidden_layers", "n_routed_experts"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["n_routed_experts_published"], cfg["experts_held"]) \
        == (5, 16, 256, [0, 16])
    assert {"apply_mla_qkv_lora_rescale", "attention_gate_type", "rope",
            "sliding_window_size", "indexer", "e_score_correction_bias",
            "towers_and_mtp"} <= set(cfg["assumed"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert entry == bench["configs"][5] and len(entry["why"]) <= 200
    cell = next(w for w in bench["workloads"] if w["config"] == NAME)
    assert (cell["name"], cell["chips"]) == (CELL, 1)
    assert cell == bench["workloads"][5] and len(cell["why"]) <= 200
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"out_tokens_per_s", "setup_s", "decode_step_ms", "slot_occupancy",
            "prefix_hit_share", "swa_kv_resident_share",
            "moe_experts_hit_share", "hbm_peak_gib.serve",
            "device_idle_pct.serve"} | set(NEW_METRICS) <= reports
    assert not {"decode_step_roofline", "moe_mla_decode_step_roofline",
                "mla_decode_kernel_roofline", "swa_decode_kernel_roofline",
                "full_decode_kernel_roofline",
                "swa_moe_decode_step_roofline"} & reports
    # PR 37's six in a row, then PR 38's selection kernel share (later
    # PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    ours = bench["per_layer"][first:first + 7]
    assert [m["name"] for m in ours] == NEW_METRICS + [
        "dsa_select_kernel_share"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
               for m in ours)
    assert {k: v["value"] for k, v in cfg["overrides"].items()} \
        == {"prefill_chunk": 512}
    # both pools and the weights, as a deployment would hold them
    dep = cfg["deployment"]
    pools = dep["num_blocks"] * 16 * 1536 * 2 \
        + dep["window_blocks"] * 16 * 2304 * 3
    assert 0.25 * 16e9 < pools + 2 * cfg["sizes"]["parameters_total"] < 14e9
    assert dep["window_blocks"] >= 1 + dep["num_slots"] * (65 + 32)
    assert dep["max_len"] >= 33280 + 1152


def test_configuration_builds_the_class_it_names(cfg):
    from benchmark import weights_by_class
    c = weights_by_class.model_config(
        cfg, n_routed_experts=f.router_width(cfg),
        experts_held=tuple(cfg["experts_held"]))
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok,
            c.num_hidden_layers, c.dtype) == (256, (0, 16), 8, 5, "bfloat16")
    assert c.layer_types == ("full_attention", "full_attention",
                             "sliding_attention", "sliding_attention",
                             "sliding_attention")
    assert c.model_class.endswith(":Dots3NoteForCausalLM")
    assert set(c.init_overrides) == {"e_score_correction_bias"}
    full, swa = c.attention_sizes(0), c.attention_sizes(1)
    assert (full["heads"], full["kv_lora_rank"], full["qk_nope_head_dim"],
            full["rope_theta"], full["window"]) == (128, 512, 128, 8e7, None)
    assert (swa["heads"], swa["kv_lora_rank"], swa["qk_nope_head_dim"],
            swa["rope_theta"], swa["window"], swa["indexer"]) \
        == (64, 1024, 192, 5e4, 513, None)
    assert full["indexer"] == {"n_heads": 64, "head_dim": 128, "topk": 2048}
    assert full["q_rescale"] == pytest.approx(5 ** 0.5)
    assert full["kv_rescale"] == pytest.approx(10 ** 0.5)
    assert (c.latent_row(0), c.latent_row(1)) == (640, 1152)


def test_cell_file_is_the_issues_traffic():
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as fh:
        cell = json.load(fh)
    t = cell["traffic"]
    assert (cell["kind"], cell["reference"], cell["generator"]) \
        == ("serve_sparse_latent_moe", "dots3_note", "general")
    assert t["arrivals"] == {"process": "backlog", "depth": 8}
    assert t["shared_prefix"] == {"share": 1.0, "len": 32768, "count": 4}
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (32832, 33280)
    assert (t["output_len"]["lo"], t["output_len"]["hi"]) == (384, 1152)
    assert (t["first_wave_output_len"]["lo"],
            t["first_wave_output_len"]["hi"]) == (1, 1152)
    assert (t["first_wave"], t["pool"], t["shape_seed"]) == (64, 128, 1)
    assert (cell["warm_s"], cell["drain_s"], cell["trace_s"]) == (30, 0, 4)
    assert "index_topk" in cell["why"] and "who" in cell
