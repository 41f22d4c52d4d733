"""The benchmark's arithmetic for the latent-cache, routed-expert cell:
sizes from shapes, a kernel's device time summed over its sites, and the
configuration file held to the catalog row it was taken from."""
import json
import os

import pytest

from benchmark import flops_latent_moe as flm
from benchmark import trace_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kanana-2-30b-a3b-instruct-2601"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


def test_sizes_from_shapes(cfg):
    """The issue's arithmetic, from the file: attention 26.35M, an expert
    4.72M, an expert layer 640.0M with 36.05M outside its experts, the
    dense layer 64.1M, embedding + head 525.3M, 5,069.6M in all."""
    assert flm.attention_params(cfg) == 12582912 + 1179648 + 512 \
        + 4194304 + 8388608
    assert flm.expert_params(cfg) == 3 * 2048 * 768
    assert flm.moe_layer_fixed_params(cfg) == 36049536
    assert flm.dense_layer_params(cfg) == 64098816
    assert flm.layers(cfg) == (1, 7)
    assert flm.total_params(cfg) == cfg["sizes"]["parameters_total"] \
        == 5069642624
    assert flm.latent_bytes_per_token_per_layer(cfg) == 1152 \
        == cfg["sizes"]["latent_bytes_per_token_per_layer_required"]
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    row = DeepseekV3Config(kv_lora_rank=512, qk_rope_head_dim=64).latent_row
    assert 2 * row == 1280 \
        == cfg["sizes"]["latent_bytes_per_token_per_layer_stored"]


def test_decode_step_bytes_follow_the_routing(cfg):
    none = flm.decode_step_bytes(cfg, 0, 0)
    assert none == 2 * (64098816 + 7 * 36049536 + 2048 * 128256 + 2048)
    one_more = flm.decode_step_bytes(cfg, 0, 1) - none
    assert one_more == 2 * 4718592                 # one expert's bytes
    assert flm.decode_step_bytes(cfg, 1000, 0) - none == 1000 * 1152 * 8
    # the issue's reckoning at 64 slots: 95% of 7 x 128 experts, 4.2k rows
    # a slot: about 11.7 GB, a 14.3 ms floor at 819 GB/s
    full = flm.decode_step_bytes(cfg, 64 * 4200, 0.95 * 7 * 128)
    assert 11.5e9 < full < 11.9e9
    assert flm.mla_decode_kernel_bytes(cfg, 64 * 4200) == 64 * 4200 * 1152
    assert flm.decode_step_flops(cfg, 64, 64 * 4200) > 0


def test_kernel_seconds_sum_over_every_site():
    ops = [("%mla_paged_attention_decode.3 = bf16[64,32,512] custom-call(q)",
            0, 400_000), ("%fusion.7 = f32[] fusion()", 400_000, 100_000),
           ("%mla_paged_attention_decode.11 = bf16[64,32,512] custom-call(q)",
            500_000, 600_000),
           ("%paged_attention_decode.2 = bf16[] custom-call(q)", 2_000_000,
            50_000)]
    loaded = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}}}
    out = trace_kernels.seconds_by_prefix(
        loaded, ("mla_paged_attention_decode", "ragged-dot"))
    assert out["mla_paged_attention_decode"] == (2, pytest.approx(1e-3))
    assert out["ragged-dot"] == (0, 0.0)
    assert trace_kernels.seconds_by_prefix({}, ("x",)) == {"x": (0, 0.0)}


def test_configuration_file_keeps_every_published_key(cfg):
    """The catalog row's ``config`` under the same keys; only the depth is
    cut, and the file says how."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 8
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    cell = next(w for w in bench["workloads"] if w["config"] == NAME)
    assert (cell["name"], cell["chips"]) == ("kanana2-docqa-decode", 1)
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if cell["name"] in m.get("workloads", [cell["name"]])}
    assert {"out_tokens_per_s", "gap_ms_p95", "setup_s",
            "moe_mla_decode_step_roofline", "mla_decode_kernel_roofline",
            "moe_experts_hit_share", "prefix_hit_share"} <= reports
    assert "decode_step_roofline" not in reports     # its bytes are Llama's
    # the one override: at the default 32-token chunk both end-to-end
    # metrics spread too widely between seeds for the cell to be admitted
    # (PERF.md section 6, PR 27)
    assert set(cfg["overrides"]) == {"prefill_chunk"}
    assert cfg["overrides"]["prefill_chunk"]["value"] == 512


def test_configuration_builds_the_class_it_names(cfg):
    from benchmark import weights_by_class
    c = weights_by_class.model_config(cfg, num_hidden_layers=2)
    assert (c.n_routed_experts, c.num_experts_per_tok, c.kv_lora_rank,
            c.q_lora_rank, c.dtype) == (128, 6, 512, None, "bfloat16")
    assert c.model_class.endswith(":DeepseekV3ForCausalLM")
