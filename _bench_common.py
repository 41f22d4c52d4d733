"""Shared plumbing for the measurement tools (bench.py,
bench_workloads.py): compile-cache setup and the headline config."""
from __future__ import annotations


def configure_jax():
    """Place the persistent compile cache (one rule for every entry
    point: paddle_tpu/utils/compile_cache.py). Returns the jax module."""
    import jax
    from paddle_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    return jax


def headline_big_config(recompute_granularity: str = "full"):
    """THE ~0.95B headline shape (single source of truth: bench.py's
    config_big and any profile of it must measure the same program)."""
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=2048,
        tensor_parallel=False, recompute=True,
        recompute_granularity=recompute_granularity,
        scan_layers=True, dtype="bfloat16")
