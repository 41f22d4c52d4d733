"""Fingerprints of the paged engine's two programs (decode block, prefill
chunk) for the model classes the benchmark's cells serve through
``PagedModelStepBackend`` (dense, latent + experts, looped) or
``HybridPagedStepBackend`` (learned sparse attention over a latent cache), at tiny
sizes on the CPU: the StableHLO text with source locations stripped, the
Pallas kernels in interpret mode so that their bodies are in the text.

    python3 tools/program_identity.py            # {"llama.block": sha1, ...}

A change that must leave those cells' programs as they are (a new model
class beside them, a new option they do not set) prints the same
fingerprints before and after; ``tests/test_program_identity.py`` pins them.
A change that means to alter a program updates the pin and says so."""
import hashlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fingerprints() -> dict:
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM,
                                               deepseek_v3_tiny_config)
    from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                              dots3_note_tiny_config)
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
    from paddle_tpu.ops.pallas import flash_attention, fused
    from paddle_tpu.serving.hybrid import HybridPagedStepBackend
    from paddle_tpu.serving.paging import PagedModelStepBackend
    fused._FORCE_INTERPRET = flash_attention._FORCE_INTERPRET = True

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    def texts(model, kv_int8=False, hybrid=False):
        if hybrid:      # two groups of cache layers: the hybrid backend
            be = HybridPagedStepBackend(model, 2, 64, 4, 8, 17, 11, 8)
        else:
            be = PagedModelStepBackend(model, 2, 64, decode_block=4,
                                       block_size=8, num_blocks=17,
                                       kv_int8=kv_int8, prefill_chunk=8)
        yield "block", be._block_jit.lower(
            be._pv, be._bv, be.pool_cache(), be.init_state()).as_text()
        yield "chunk", be._chunk_jit.lower(
            be._pv, be._bv, i32(1, 8), be.pool_cache(),
            i32(1, be.table_width), jnp.int32(0), jnp.int32(8),
            jax.random.PRNGKey(0), jnp.float32(0), jnp.int32(0),
            jnp.float32(1)).as_text()

    paddle.seed(0)
    llama = LlamaForCausalLM(llama_tiny_config(tensor_parallel=False))
    latent = DeepseekV3ForCausalLM(deepseek_v3_tiny_config())
    looped = OuroForCausalLM(ouro_tiny_config())
    sparse = Dots3NoteForCausalLM(dots3_note_tiny_config())
    out = {}
    for name, programs in (("llama", texts(llama)),
                           ("llama_int8", texts(llama, kv_int8=True)),
                           ("deepseek_v3", texts(latent)),
                           ("ouro", texts(looped)),
                           ("dots3_note", texts(sparse, hybrid=True))):
        for program, text in programs:
            text = re.sub(r"loc\(.*?\)", "", text)
            out[f"{name}.{program}"] = hashlib.sha1(text.encode()).hexdigest()
    return out


if __name__ == "__main__":
    print(json.dumps(fingerprints(), indent=1))
