"""The benchmark's arithmetic and kind for the cell with gated short-conv
layers beside grouped-query attention layers and all 64 routed experts held
(``lfm2-tool-agent-decode``): sizes from shapes, the required bytes of a
step and of the conv mixers, the readers, the configuration file held to
the catalog row it was taken from, and the kind end to end on the CPU at
toy sizes."""
import argparse
import json
import os

import pytest

from benchmark import common
from benchmark import flops_conv_moe as f

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "LFM2-24B-A2B"
CELL = "lfm2-tool-agent-decode"
NEW_METRICS = ["conv_moe_decode_step_roofline", "short_conv_decode_roofline"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_sizes_from_shapes(cfg):
    """The configuration's sizes: a conv mixer 16,783,360, an attention mixer
    10,485,888, the dense SwiGLU 72,351,744, an expert 9,437,184; layer 0
    89.1M, an expert conv layer 620.9M, an expert attention layer 614.6M,
    embedding + head 268.4M: 5,312,168,704 in all = 10.62 GB."""
    sizes = cfg["sizes"]
    assert f.layer_types(cfg) == ["conv"] + ["full_attention", "conv",
                                             "conv", "conv"] * 2
    assert (f.layers_of(cfg, f.CONV), f.layers_of(cfg, f.ATTN),
            f.moe_layers(cfg)) == (7, 2, 8)
    assert f.conv_params(cfg) == 12582912 + 6144 + 4194304 == 16783360 \
        == sizes["conv_mixer_parameters"]
    assert f.attention_params(cfg) == 4194304 + 2 * 1048576 + 4194304 \
        + 128 == 10485888 == sizes["attention_mixer_parameters"]
    assert f.expert_params(cfg) == 9437184 == sizes["one_expert_parameters"]
    assert f.router_params(cfg) == 131072 + 64
    assert f.total_params(cfg) == 5312168704 == sizes["parameters_total"]
    assert round(f.total_params(cfg) * 2 / 1e9, 2) \
        == sizes["weights_gb_bf16"] == 10.62
    assert 2 * f.kv_bytes_per_token(cfg) == 4096 \
        == sizes["kv_bytes_per_token"]
    assert f.conv_state_bytes(cfg) == 7 * 2 * 2048 * 2 == 57344 \
        == sizes["conv_state_bytes_per_block"]


def test_model_holds_what_the_arithmetic_counts(cfg):
    """The class built on abstract weights at the served cut holds exactly
    ``total_params`` parameters and the layers of the cut."""
    from benchmark import weights_by_class
    from paddle_tpu.models.lfm2_moe import Lfm2MoeForCausalLM
    from paddle_tpu.utils.scale import abstract_init
    c = weights_by_class.model_config(cfg)
    with abstract_init("bfloat16"):
        model = Lfm2MoeForCausalLM(c)
    assert model.num_params() == f.total_params(cfg)
    assert (c.num_experts, c.num_experts_per_tok, c.num_hidden_layers,
            c.num_dense_layers, c.dtype, c.head_dim, c.conv_layers) \
        == (64, 4, 9, 1, "bfloat16", 64, 7)
    assert c.layer_types == tuple(cfg["layer_types_published"][i]
                                  for i in cfg["layers_kept"])
    assert c.init_overrides == {}
    assert cfg["init_quantiles"] == {"e_score_correction_bias": {
        "normal_std": 0.05}}


def test_selection_bias_is_a_fixed_set_in_a_seeds_order(cfg):
    """``init_quantiles``: each expert layer's selection bias holds the
    mid-point quantiles of N(0, 0.05) whatever the seed; the seed and the
    layer decide only their order, and a seed gives the same values again.
    The toy model of ``lfm2_toy.json`` (8 experts, 4 expert layers)."""
    import numpy as np
    from benchmark import weights_by_class, weights_quantiles as wq
    c = common.merge(cfg, common.load_json("tests", "lfm2_toy.json")[
        "config"])
    law = wq.quantiles(8, 0.05)
    assert np.all(np.diff(law) > 0) and abs(law.mean()) < 1e-12
    assert 0.9 * 0.05 < law.std() < 0.05

    def biases(seed):
        model = weights_by_class.build_lazy(weights_by_class.model_config(c),
                                            seed)
        names = wq.apply(model, c, seed)
        got = {k: np.asarray(p._value) for k, p in model.named_parameters()
               if k.endswith("e_score_correction_bias")}
        assert sorted(names) == sorted(got) and len(got) == 4
        return [got[k] for k in sorted(got)]

    one, again, other = biases(3000000019), biases(3000000019), \
        biases(2 ** 33 + 5)
    for b in one + other:
        assert b.dtype == np.float32
        np.testing.assert_allclose(np.sort(b), law, rtol=1e-6)
    assert all(np.array_equal(a, b) for a, b in zip(one, again))
    assert not all(np.array_equal(a, b) for a, b in zip(one, other))
    assert len({tuple(b) for b in one}) == 4        # each layer its own order
    # a configuration without the key is left as built
    model = weights_by_class.build_lazy(weights_by_class.model_config(c), 1)
    assert wq.apply(model, {}, 1) == []


def test_decode_step_bytes_follow_the_routing_and_the_slots(cfg):
    none = f.decode_step_bytes(cfg, 0, 0, 0)
    assert none == 2 * f.fixed_params(cfg)
    assert f.decode_step_bytes(cfg, 0, 1, 0) - none == 2 * 9437184 \
        == f.expert_bytes(cfg, 1)
    # a live row costs 2,048 B in each of the 2 attention layers; a
    # decoding slot reads one block's 57,344 B of conv state and writes
    # another's
    assert f.decode_step_bytes(cfg, 1000, 0, 0) - none == 1000 * 2048 * 2
    assert f.decode_step_bytes(cfg, 0, 0, 1) - none == 2 * 57344
    # the cell's reckoning: 128 slots at about 4.7k, every expert hit in
    # 8 layers: about 12.8 GB, 15.6 ms at 819 GB/s
    full = f.decode_step_bytes(cfg, 128 * 4700, 8 * 64, 128)
    assert 12.6e9 < full < 13.0e9
    assert 9.6e9 < f.expert_bytes(cfg, 8 * 64) < 9.7e9
    # the conv mixers: 7 x 16,783,360 x 2 B = 235 MB, and 128 slots' state
    # both ways 14.7 MB
    assert f.short_conv_decode_bytes(cfg, 0) == 7 * 16783360 * 2
    assert f.short_conv_decode_bytes(cfg, 128) \
        - f.short_conv_decode_bytes(cfg, 0) == 128 * 2 * 57344


def test_readers_divide_by_their_own_work(cfg):
    """128 slots at 4.7k: a step of 16 ms and 0.4 ms a step under the conv
    scope; the readers decline, never raise, without the counters, the
    trace or this family's configuration."""
    peaks = {"hbm_bytes_per_s": 819e9}
    window = {"decode_module": "jit_block_fn", "decode_block": 8,
              "kv_rows_per_step": 128 * 4700,
              "moe_expert_hits_per_step": 512.0,
              "live_slots_per_step": 127.5}
    ctx = argparse.Namespace(
        window=window, peaks=peaks, config=cfg,
        trace_summary={"modules": {"jit_block_fn": (10, 10 * 8 * 0.016)}},
        scope_seconds={"short_conv": (700, 80 * 4e-4)})

    def read(name):
        return common.load_module("layer_metrics", name + ".py").read(ctx)
    step = (2 * f.fixed_params(cfg) + 512 * 2 * 9437184
            + 2 * 128 * 4700 * 2048 + 2 * 127.5 * 57344)
    assert read("conv_moe_decode_step_roofline") == pytest.approx(
        step / 819e9 / 0.016 * 100)
    assert read("short_conv_decode_roofline") == pytest.approx(
        (7 * 16783360 * 2 + 2 * 127.5 * 57344) / 819e9 / 4e-4 * 100)
    assert all(0 < read(n) < 100 for n in NEW_METRICS)
    empty = argparse.Namespace(window={}, trace_summary={}, peaks=None,
                               config={}, scope_seconds=None)
    ctx = empty
    assert all(read(n) is None for n in NEW_METRICS)
    # another family's configuration has no conv: declined too
    ctx = argparse.Namespace(**dict(vars(empty), window=window, peaks=peaks,
                                    config={"num_hidden_layers": 7}))
    assert all(read(n) is None for n in NEW_METRICS)


def test_configuration_file_keeps_every_published_key(cfg, bench):
    """The catalog row's ``config`` under the same keys, rope whole; only
    the depth, the leading dense layers counted once and the layer list at
    the layers kept are cut, and the file says how."""
    types = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                "conv"] * 9 + ["full_attention", "conv"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": types,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) \
        == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    assert cfg["layer_types_published"] == types
    assert cfg["layers_kept"] == [0] + list(range(2, 10))
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (9, 1)
    assert {"weights", "head", "intermediate_size", "expert_bias_use",
            "qk_norm", "conv", "sampling"} <= set(cfg["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types"]
    assert entry == bench["configs"][7] and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"]
    assert {k: v["value"] for k, v in cfg["overrides"].items()} \
        == {"prefill_chunk": 512}
    # the pool and the weights, as a deployment would hold them
    dep = cfg["deployment"]
    pool = dep["num_blocks"] * (16 * 4096 + 57344)
    assert 12.5e9 < pool + 2 * cfg["sizes"]["parameters_total"] < 12.7e9
    assert dep["num_blocks"] - 1 >= 4 * 256 + dep["num_slots"] * 96
    assert (dep["num_slots"], dep["max_len"]) == (128, 6144)
    assert dep["check_cut"] % 16 == 0 and dep["check_context"] \
        - dep["check_cut"] <= cfg["overrides"]["prefill_chunk"]["value"]


def test_the_cell_and_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["config"] == NAME)
    assert (cell["name"], cell["chips"], cell["traffic"]) \
        == (CELL, 1, "tool-agent-decode")
    assert cell == bench["workloads"][7] and len(cell["why"]) <= 200
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"out_tokens_per_s", "setup_s", "decode_step_ms", "slot_occupancy",
            "prefix_hit_share", "moe_experts_hit_share",
            "hbm_peak_gib.serve", "device_idle_pct.serve",
            "tick_sched_ms.serve", "tick_dispatch_ms.serve",
            "tick_device_wait_ms.serve", "tick_harvest_ms.serve",
            "device_starved_ms.serve", "sync_tail_ms.serve",
            "admit_ms_per_request.serve",
            "chunk_dispatch_ms_per_chunk.serve", "between_ticks_ms.serve",
            "gap_ms_p95", "tick_ms_p95.serve"} | set(NEW_METRICS) <= reports
    # readers of other families' keys (Laguna's expert scope reads a
    # Laguna configuration) and of a dense model's step stay out
    assert not {"decode_step_roofline", "moe_experts_decode_roofline",
                "gated_moe_decode_step_roofline", "swa_kv_resident_share",
                "train_tokens_per_s"} & reports
    ours = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in ours] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
               and (m["unit"], m["source"], m["layer"], m["better"])
               == ("%", "device_trace", "kernels", "higher") for m in ours)
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 64 * 1024


def test_cell_file_is_the_tool_agent_traffic():
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as fh:
        cell = json.load(fh)
    t = cell["traffic"]
    assert (cell["kind"], cell["reference"], cell["generator"]) \
        == ("serve_conv_moe", "lfm2_moe", "general")
    assert t["arrivals"] == {"process": "backlog", "depth": 8}
    assert t["shared_prefix"] == {"share": 1.0, "len": 4096, "count": 4}
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"]) == (4160, 4608)
    assert (t["output_len"]["lo"], t["output_len"]["hi"]) == (256, 1024)
    assert (t["first_wave_output_len"]["lo"],
            t["first_wave_output_len"]["hi"]) == (1, 1024)
    assert (t["first_wave"], t["pool"], t["shape_seed"]) == (128, 256, 1)
    assert (cell["warm_s"], cell["drain_s"], cell["trace_s"]) == (30, 0, 4)


def test_kind_runs_the_cell_on_the_cpu_at_toy_sizes(monkeypatch):
    """``correct``'s checks, both premises and every reader of the cell,
    through the kind at ``lfm2_toy.json`` sizes
    (``benchmark/tests/test_conv_moe.py``)."""
    toy = common.load_module("tests", "test_conv_moe.py")
    toy.check_toy_run(*toy.run_toy_cell(monkeypatch))
