"""The names a device trace shows, pinned: the three compiled programs
``benchmark/kinds/*.py`` reads device time by (``jit_block_fn``,
``jit_chunk_fn``, ``jit_step``), the ``name`` of every Pallas kernel under
``ops/pallas`` (the trace's ``<name>.<n>`` operations; ``closed_call.<n>``
without one), and that the ``jax.named_scope``s at the model's layer
boundaries are metadata only — the compiled programs are the same
programs with and without them. A rename here turns a per-layer metric of
the benchmark into ``null``: change the benchmark in the same PR."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import moe_dispatch as md
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import xent
from paddle_tpu.serving.quant import QuantConfig


@pytest.fixture
def interpret(monkeypatch):
    """Open every Pallas gate off-TPU (interpret mode); nothing here runs
    a kernel — the programs are only traced."""
    monkeypatch.setattr(fused, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(md, "_FORCE_INTERPRET", True)


def pallas_names(closed) -> list:
    """The ``name`` of every ``pallas_call`` in a jaxpr, sub-jaxprs
    (jit, custom_vjp, scan, ...) included, kernel bodies not."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
                continue
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(closed.jaxpr)
    return out


def f32(*shape):
    return jnp.ones(shape, jnp.float32)


def i32(*shape):
    return jnp.zeros(shape, jnp.int32)


def _paged():
    return jax.make_jaxpr(lambda *a: pa.paged_attention_decode(
        *a, scale=0.1))(f32(2, 4, 32), f32(5, 8, 2, 32), f32(5, 8, 2, 32),
                        i32(2, 3), i32(2))


def _latent():
    return jax.make_jaxpr(lambda *a: pa.mla_paged_attention_decode(
        *a, scale=0.1, rank=96))(f32(2, 4, 128), f32(5, 8, 128), i32(2, 3),
                                 i32(2))


def _packed():
    return jax.make_jaxpr(lambda *a: pa.packed_paged_attention_decode(
        *a, scale=0.1, kvh=2, dv=32))(f32(2, 4, 128), f32(5, 16, 128),
                                      i32(2, 3), i32(2))


def _swa():
    return jax.make_jaxpr(lambda *a: pa.swa_paged_attention_decode(
        *a, scale=0.1, kvh=2, dv=32, window=12))(
            f32(2, 4, 128), f32(5, 16, 128), i32(2, 3), i32(2), f32(4))


def _swa_mla():
    return jax.make_jaxpr(lambda *a: pa.swa_mla_paged_attention_decode(
        *a, scale=0.1, rank=96, window=12))(
            f32(2, 4, 128), f32(5, 8, 128), i32(2, 3), i32(2))


def _dsa_index():
    return jax.make_jaxpr(pa.dsa_index_scores_decode)(
        f32(2, 4, 16), f32(2, 4), f32(5, 8, 16), i32(2, 3), i32(2))


def _dsa_sparse():
    return jax.make_jaxpr(lambda *a: pa.dsa_sparse_mla_decode(
        *a, scale=0.1, rank=96))(f32(2, 4, 128), f32(5, 8, 128), i32(2, 3),
                                 i32(2, 6), i32(2))


def _paged_int8():
    codes = jnp.zeros((5, 8, 2, 32), jnp.int8)
    return jax.make_jaxpr(lambda *a: pa.paged_attention_decode_int8(
        *a, scale=0.1))(f32(2, 4, 32), codes, codes, f32(5, 8, 2),
                        f32(5, 8, 2), i32(2, 3), i32(2))


def _flash_fwd_bwd():
    q = f32(1, 64, 2, 16)
    return jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention_fused(*a, True).sum(),
        argnums=(0, 1, 2)))(q, q, q)


KERNELS = {
    "paged_attention_decode": (_paged, ["paged_attention_decode"]),
    "paged_attention_decode_int8":
        (_paged_int8, ["paged_attention_decode_int8"]),
    "mla_paged_attention_decode": (_latent, ["mla_paged_attention_decode"]),
    # a packed arena's full-layer read keeps the paged kernel's name; its
    # window-layer read has a name of its own
    "paged_attention_decode-packed": (_packed, ["paged_attention_decode"]),
    "swa_paged_attention_decode": (_swa, ["swa_paged_attention_decode"]),
    # PR 37: the three reads of learned sparse attention over a latent cache
    "swa_mla_paged_attention_decode":
        (_swa_mla, ["swa_mla_paged_attention_decode"]),
    "dsa_index_scores_decode": (_dsa_index, ["dsa_index_scores_decode"]),
    "dsa_sparse_mla_decode": (_dsa_sparse, ["dsa_sparse_mla_decode"]),
    "fused_rms_norm": (lambda: jax.make_jaxpr(
        lambda x, w: fused.fused_rms_norm(x, w, 1e-5))(
            f32(4, 128), f32(128)), ["fused_rms_norm"]),
    "fused_rms_norm_residual": (lambda: jax.make_jaxpr(
        lambda x, r, w: fused.fused_rms_norm(x, w, 1e-5, residual=r))(
            f32(4, 128), f32(4, 128), f32(128)),
        ["fused_rms_norm_residual"]),
    "fused_rope": (lambda: jax.make_jaxpr(fused.fused_rope)(
        f32(1, 8, 2, 32), f32(1, 8, 2, 32), f32(8, 32), f32(8, 32)),
        ["fused_rope"]),
    "fused_adamw": (lambda: jax.make_jaxpr(
        lambda p, g, m, v: fused.fused_adamw(
            p, g, m, v, 1e-3, weight_decay=0.01, step=2))(
                *(f32(8, 128),) * 4), ["fused_adamw"]),
    "flash_attention": (_flash_fwd_bwd, [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
        "flash_attention_fwd"]),
    "fused_xent_fwd": (lambda: jax.make_jaxpr(xent._rows_pallas_fwd)(
        f32(8, 256), i32(8)), ["fused_xent_fwd"]),
    "fused_xent_bwd": (lambda: jax.make_jaxpr(xent._rows_pallas_bwd)(
        f32(8, 256), i32(8), f32(8), f32(8), f32(8)), ["fused_xent_bwd"]),
    "moe_gather_rows": (lambda: jax.make_jaxpr(md._gather_rows_pallas)(
        f32(16, 128), i32(8)), ["moe_gather_rows"]),
    "moe_gather_rows_mr": (lambda: jax.make_jaxpr(
        lambda x, i: md._gather_rows_pallas_mr(x, i, rows_per_step=4))(
            f32(16, 128), i32(8)), ["moe_gather_rows_mr"]),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_pallas_kernel_is_named(kernel, interpret):
    trace, want = KERNELS[kernel]
    assert sorted(set(pallas_names(trace()))) == want


def test_every_pallas_call_site_passes_a_name():
    """A new kernel without ``name=`` would be ``closed_call.<n>`` in the
    trace: the source of ops/pallas is checked site by site."""
    import glob
    import os
    root = os.path.dirname(fused.__file__)
    sites = 0
    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        src = open(path).read()
        for m in re.finditer(r"pl\.pallas_call\(", src):
            depth, i = 1, m.end()
            while depth:                      # to the matching ")"
                depth += {"(": 1, ")": -1}.get(src[i], 0)
                i += 1
            assert "name=" in src[m.end():i], \
                f"{os.path.basename(path)}: pallas_call without name="
            sites += 1
    assert sites >= 10


# -- the three program names the benchmark reads ---------------------------

@pytest.fixture(scope="module")
def tiny():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny_config(tensor_parallel=False))


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _paged_backend(model, kv_int8=False, **kw):
    from paddle_tpu.serving.paging import PagedModelStepBackend
    return PagedModelStepBackend(model, 2, 64, decode_block=4,
                                 block_size=8, num_blocks=17,
                                 kv_int8=kv_int8, prefill_chunk=8, **kw)


def _lower_block(be):
    return be._block_jit.lower(be._pv, be._bv, be.pool_cache(),
                               be.init_state())


def _lower_chunk(be):
    return be._chunk_jit.lower(
        be._pv, be._bv, i32(1, 8), be.pool_cache(), i32(1, be.max_blocks),
        jnp.int32(0), jnp.int32(8), jax.random.PRNGKey(0),
        jnp.float32(0), jnp.int32(0), jnp.float32(1))


def _dense(model):
    from paddle_tpu.serving.engine import ModelStepBackend
    return _lower_block(ModelStepBackend(model, 2, 64, decode_block=4)), None


def _paged_programs(**kw):
    def build(model):
        be = _paged_backend(model, **kw)
        return _lower_block(be), _lower_chunk(be)
    return build


def _spec_verify(model):
    from paddle_tpu.serving.spec import SpecConfig, SpecPagedStepBackend
    be = SpecPagedStepBackend(model, 2, 64, 4, 8, 17, False, 8,
                              SpecConfig(k=4))
    return be._spec_jit.lower(be._pv, be._bv, be.pool_cache(),
                              be.init_state(), i32(2, 4), i32(2)), \
        _lower_chunk(be)


def _tp_sharded(model):
    """The shard_map programs over the suite's 8 virtual devices: their
    own model (the mesh shards 8 kv heads)."""
    from paddle_tpu.distributed.mesh import build_device_mesh
    from paddle_tpu.serving.tp import ShardedPagedStepBackend, TPConfig
    if jax.device_count() < 8:
        pytest.skip("needs 8 (simulated) devices for the 2x4 mesh")
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(num_attention_heads=8,
                                               num_key_value_heads=8))
    be = ShardedPagedStepBackend(
        model, 2, 64, 4, 8, 17, False, 8,
        TPConfig(axes=("dp", "mp"),
                 mesh=build_device_mesh({"dp": 2, "mp": 4})))
    return _lower_block(be), _lower_chunk(be)


DECODE_BUILDERS = {
    "dense": _dense,
    "paged": _paged_programs(),
    "paged-kv_int8": _paged_programs(kv_int8=True),
    "weight-quant": _paged_programs(quant=QuantConfig(weights="int8")),
    "spec-verify": _spec_verify,
    "tp-sharded": _tp_sharded,
}


@pytest.mark.parametrize("builder", list(DECODE_BUILDERS))
def test_decode_and_prefill_programs_are_named_on_purpose(tiny, builder):
    """EVERY backend that builds a decode-block program names it
    ``engine.DECODE_PROGRAM`` (the speculative engine's verify step and
    the sharded block included), and its chunk program
    ``engine.PREFILL_CHUNK_PROGRAM``: the benchmark's ``decode_step_ms``
    sums device time by that name, and reads 0 for any other."""
    from paddle_tpu.serving import engine
    assert (engine.DECODE_PROGRAM, engine.PREFILL_CHUNK_PROGRAM) \
        == ("jit_block_fn", "jit_chunk_fn")
    block, chunk = DECODE_BUILDERS[builder](tiny)
    assert _module_name(block) == engine.DECODE_PROGRAM
    if chunk is not None:
        assert _module_name(chunk) == engine.PREFILL_CHUNK_PROGRAM


@pytest.fixture(scope="module")
def tiny_latent_moe():
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM,
                                               deepseek_v3_tiny_config)
    paddle.seed(0)
    return DeepseekV3ForCausalLM(deepseek_v3_tiny_config())


def test_latent_moe_model_keeps_the_program_names_and_names_its_parts(
        tiny_latent_moe, interpret):
    """A model of another class runs the SAME two programs (the benchmark
    reads ``jit_block_fn`` / ``jit_chunk_fn`` whatever the model), its
    s = 1 read is the named latent kernel, and its layer parts carry the
    scopes a trace is read by."""
    from paddle_tpu.serving import engine
    be = _paged_backend(tiny_latent_moe)
    block, chunk = _lower_block(be), _lower_chunk(be)
    assert _module_name(block) == engine.DECODE_PROGRAM
    assert _module_name(chunk) == engine.PREFILL_CHUNK_PROGRAM
    cache = tuple(jnp.zeros(s, d) for s, d in be.pool_specs)
    names = pallas_names(jax.make_jaxpr(be._block_jit)(
        be._pv, be._bv, cache, be.init_state()))
    assert "mla_paged_attention_decode" in names
    assert "paged_attention_decode" not in names
    text = block.as_text(debug_info=True)
    for scope in ("attn", "mlp", "moe_router", "moe_experts", "moe_shared",
                  "lm_head", "sample"):
        assert f"{scope}/" in text, scope
    # the s > 1 read gathers: no latent kernel in the chunk program
    assert "mla_paged_attention_decode" not in pallas_names(
        jax.make_jaxpr(be._chunk_jit)(
            be._pv, be._bv, i32(1, 8), cache, i32(1, be.max_blocks),
            jnp.int32(0), jnp.int32(8), jax.random.PRNGKey(0),
            jnp.float32(0), jnp.int32(0), jnp.float32(1)))


def test_hybrid_model_keeps_the_program_names_and_names_both_reads(
        interpret):
    """A model with window and full layers runs the SAME two programs; its
    full layers' s = 1 read keeps ``paged_attention_decode``, its window
    layers' is ``swa_paged_attention_decode``; window layers sit under the
    scope ``attn_swa`` beside ``attn``; the chunk program gathers."""
    from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                           mimo_v2_tiny_config)
    from paddle_tpu.serving import engine
    from paddle_tpu.serving.hybrid import HybridPagedStepBackend
    paddle.seed(0)
    be = HybridPagedStepBackend(MiMoV2ForCausalLM(mimo_v2_tiny_config()), 2,
                                64, 4, 8, 17, 11, 8)
    block, chunk = _lower_block(be), be._chunk_jit.lower(
        be._pv, be._bv, i32(1, 8), be.pool_cache(), i32(1, be.table_width),
        jnp.int32(0), jnp.int32(8), jax.random.PRNGKey(0),
        jnp.float32(0), jnp.int32(0), jnp.float32(1))
    assert _module_name(block) == engine.DECODE_PROGRAM
    assert _module_name(chunk) == engine.PREFILL_CHUNK_PROGRAM
    cache = tuple(jnp.zeros(s, d) for s, d in be.pool_specs)
    names = pallas_names(jax.make_jaxpr(be._block_jit)(
        be._pv, be._bv, cache, be.init_state()))
    assert names.count("paged_attention_decode") == 2
    assert names.count("swa_paged_attention_decode") == 5
    text = block.as_text(debug_info=True)
    for scope in ("attn", "attn_swa", "mlp", "moe_router", "moe_experts",
                  "lm_head", "sample"):
        assert f"{scope}/" in text, scope
    assert "moe_shared/" not in text            # this family has none


def test_sparse_latent_model_keeps_the_program_names_and_names_its_reads(
        interpret):
    """A model with learned sparse attention over a latent cache through
    the hybrid backend: the two programs keep the names the trace is read
    by; a full layer's decode calls ``dsa_index_scores_decode`` and
    ``dsa_sparse_mla_decode``, a sliding layer's
    ``swa_mla_paged_attention_decode``; the indexer, the selection, the
    selected read and the gate sit under scopes of their own."""
    from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                              dots3_note_tiny_config)
    from paddle_tpu.serving import engine
    from paddle_tpu.serving.hybrid import HybridPagedStepBackend
    paddle.seed(0)
    be = HybridPagedStepBackend(
        Dots3NoteForCausalLM(dots3_note_tiny_config()), 2, 64, 4, 8, 17, 11,
        8)
    assert be.leaf_group == (0, 0, 0, 0, 1, 1, 1, None)
    block, chunk = _lower_block(be), be._chunk_jit.lower(
        be._pv, be._bv, i32(1, 8), be.pool_cache(), i32(1, be.table_width),
        jnp.int32(0), jnp.int32(8), jax.random.PRNGKey(0),
        jnp.float32(0), jnp.int32(0), jnp.float32(1))
    assert _module_name(block) == engine.DECODE_PROGRAM == "jit_block_fn"
    assert _module_name(chunk) == engine.PREFILL_CHUNK_PROGRAM == "jit_chunk_fn"
    cache = tuple(jnp.zeros(s, d) for s, d in be.pool_specs)
    names = pallas_names(jax.make_jaxpr(be._block_jit)(
        be._pv, be._bv, cache, be.init_state()))
    assert names.count("dsa_index_scores_decode") == 2
    assert names.count("dsa_sparse_mla_decode") == 2
    assert names.count("swa_mla_paged_attention_decode") == 3
    assert "mla_paged_attention_decode" not in names
    text = block.as_text(debug_info=True)
    for scope in ("attn", "attn_swa", "dsa_index", "dsa_select", "dsa_read",
                  "attn_gate", "mlp", "moe_router", "moe_experts",
                  "moe_shared", "lm_head", "sample"):
        assert f"{scope}/" in text, scope
    chunk_text = chunk.as_text(debug_info=True)
    for scope in ("dsa_index", "dsa_select", "dsa_read", "attn_gate"):
        assert f"{scope}/" in chunk_text, scope


def test_sparse_latent_engine_span_and_counter_names():
    """The indexers' counters by the names PERF.md's rows give them: on the
    engine, on the spans ``serving.decode_block`` and
    ``serving.prefill_chunk`` beside the expert counters, and totalled in
    ``Server.stats()``."""
    from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                              dots3_note_tiny_config)
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    paddle.seed(0)
    eng = ContinuousBatchingEngine(
        Dots3NoteForCausalLM(dots3_note_tiny_config()), num_slots=2,
        max_len=64, decode_block=4, paged=True, block_size=8,
        prefill_chunk=8)
    assert list(eng.backend.cache_counters) == [
        "moe_picks", "moe_expert_hits", "moe_max_load", "dsa_tokens_scored",
        "dsa_tokens_selected"]
    srv = Server(eng, Scheduler())
    before = len(tracing.since(0))
    srv.submit(np.arange(20, dtype=np.int32), max_new_tokens=6)
    srv.run_until_idle()
    spans = tracing.since(0)[before:]
    blocks = [s.ids for s in spans if s.name == "serving.decode_block"
              and "dsa_tokens_scored" in s.ids]
    chunks = [s.ids for s in spans if s.name == "serving.prefill_chunk"
              and "dsa_tokens_scored" in s.ids]
    assert blocks and chunks
    assert sum(b["dsa_tokens_selected"] for b in blocks) \
        == eng.dsa_tokens_selected == 2 * sum(
            min(20 + j + 1, 16) for j in range(5))
    assert sum(b["dsa_tokens_scored"] for b in blocks) \
        == eng.dsa_tokens_scored == 2 * sum(20 + j + 1 for j in range(5))
    assert all("moe_picks" in b for b in blocks)
    stats = srv.stats()
    assert stats["dsa_tokens_scored"] == eng.dsa_tokens_scored
    assert stats["dsa_tokens_selected"] == eng.dsa_tokens_selected
    assert eng.prefill_dsa_tokens_scored == sum(
        c["dsa_tokens_scored"] for c in chunks) > 0


def test_hybrid_engine_span_and_counter_names():
    """What the hybrid engine adds to ``serving.decode_block`` and
    ``serving.admit``, by the names PERF.md's rows give them, each an
    attribute of the engine too; and the window pool's evictions beside
    ``block_evictions`` in ``Server.stats()``."""
    from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                           mimo_v2_tiny_config)
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    paddle.seed(0)
    eng = ContinuousBatchingEngine(
        MiMoV2ForCausalLM(mimo_v2_tiny_config()), num_slots=2, max_len=64,
        decode_block=4, paged=True, block_size=8, prefill_chunk=8)
    # a fresh engine's slots are all dead at pos 0: one page a slot a step
    # in each group, and no live row
    assert eng._decode_block_counters() == {
        "sampled_steps": 0, "kv_pages_live": 8, "kv_pages_copied": 8,
        "arm_ns": 0,
        "window_kv_pages_live": 8, "window_kv_pages_copied": 8,
        "kv_rows_live": 0, "window_kv_rows_live": 0}
    assert (eng.window_kv_pages_live, eng.window_kv_pages_copied,
            eng.kv_rows_live, eng.window_kv_rows_live) == (8, 8, 0, 0)
    srv = Server(eng, Scheduler())
    import time
    t0 = time.perf_counter()
    srv.submit(np.arange(19, dtype=np.int32), max_new_tokens=6)
    srv.run_until_idle()
    admit = [sp for sp in tracing.since(t0) if sp.name == "serving.admit"][0]
    assert set(admit.ids) == {"rid", "fresh_blocks", "evicted_blocks",
                              "hashed_blocks", "reserved_ns", "keyed_ns",
                              "window_blocks",
                              "shared_window_blocks",
                              "window_evicted_blocks"}
    assert (admit.ids["fresh_blocks"], admit.ids["window_blocks"]) == (3, 3)
    stats = srv.stats()
    # a cold lookup hashes the block it misses on; the life, the three
    # blocks it wrote, in the full group's manager for both groups
    assert (admit.ids["hashed_blocks"], stats["hashed_blocks"]) == (1, 3)
    assert stats["window_block_evictions"] == stats["block_evictions"] == 0


def test_looped_model_keeps_the_program_names_and_holds_each_layer_once(
        interpret):
    """A looped model runs the SAME two programs; the passes are a loop IN
    the program, so the decode block holds ``paged_attention_decode`` once
    a weight layer (3), not once a cache layer (12); a pass sits under the
    scope ``ut_step`` and the norm and gate that close it under
    ``exit_gate``; the chunk program gathers."""
    from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
    from paddle_tpu.serving import engine
    paddle.seed(0)
    be = _paged_backend(OuroForCausalLM(ouro_tiny_config()))
    assert (be.cache_passes, be.attn_sites) == (4, 12)
    block, chunk = _lower_block(be), _lower_chunk(be)
    assert _module_name(block) == engine.DECODE_PROGRAM
    assert _module_name(chunk) == engine.PREFILL_CHUNK_PROGRAM
    cache = tuple(jnp.zeros(s, d) for s, d in be.pool_specs)
    names = pallas_names(jax.make_jaxpr(be._block_jit)(
        be._pv, be._bv, cache, be.init_state()))
    assert names.count("paged_attention_decode") == 3
    for text in (block.as_text(debug_info=True),
                 chunk.as_text(debug_info=True)):
        for scope in ("ut_step/attn", "ut_step/mlp", "exit_gate", "lm_head",
                      "sample"):
            assert f"{scope}/" in text, scope
    assert "paged_attention_decode" not in pallas_names(
        jax.make_jaxpr(be._chunk_jit)(
            be._pv, be._bv, i32(1, 8), cache, i32(1, be.max_blocks),
            jnp.int32(0), jnp.int32(8), jax.random.PRNGKey(0),
            jnp.float32(0), jnp.int32(0), jnp.float32(1)))


def test_looped_engine_span_and_counter_names():
    """What a looped model adds to ``serving.decode_block`` (``ut_steps``,
    and ``ut_exit_step_milli`` from the program's counters, which
    ``serving.prefill_chunk`` carries too), each an attribute of the engine;
    ``attn_sites`` beside one site's page counts; all three on
    ``Server.stats()``."""
    import time

    from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    paddle.seed(0)
    eng = ContinuousBatchingEngine(
        OuroForCausalLM(ouro_tiny_config()), num_slots=2, max_len=64,
        decode_block=4, paged=True, block_size=8, prefill_chunk=8)
    assert eng.attn_sites == 12
    # a fresh engine's slots are all dead at pos 0: one page a slot a step
    # at ONE site, and four passes a step
    assert eng._decode_block_counters() == {
        "sampled_steps": 0, "kv_pages_live": 8, "kv_pages_copied": 8,
        "arm_ns": 0, "ut_steps": 16}
    assert (eng.ut_steps, eng.ut_exit_step_milli,
            eng.prefill_ut_exit_step_milli) == (16, 0, 0)
    srv = Server(eng, Scheduler())
    t0 = time.perf_counter()
    srv.submit(np.arange(19, dtype=np.int32), max_new_tokens=6)
    srv.run_until_idle()
    spans = tracing.since(t0)
    block = [sp for sp in spans if sp.name == "serving.decode_block"][-1]
    assert {"ut_steps", "ut_exit_step_milli", "kv_pages_live"} \
        <= set(block.ids)
    chunk = [sp for sp in spans if sp.name == "serving.prefill_chunk"
             and "chunks" in sp.ids][-1]
    assert "ut_exit_step_milli" in chunk.ids
    stats = srv.stats()
    assert stats["attn_sites"] == 12
    assert stats["ut_steps"] == eng.ut_steps == 16 + 4 * eng.steps
    assert stats["ut_exit_step_milli"] == eng.ut_exit_step_milli > 0


def test_a_model_that_does_not_loop_says_so_nowhere(tiny):
    """``attn_sites`` is the layers of a model that runs each once, and
    neither ``ut_steps`` nor ``ut_exit_step_milli`` is on its stats."""
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    eng = ContinuousBatchingEngine(tiny, num_slots=2, max_len=64,
                                   decode_block=4, paged=True,
                                   block_size=8, prefill_chunk=8)
    assert (eng.cache_passes, eng.attn_sites) \
        == (1, tiny.config.num_hidden_layers)
    stats = Server(eng, Scheduler()).stats()
    assert stats["attn_sites"] == tiny.config.num_hidden_layers
    assert not {"ut_steps", "ut_exit_step_milli"} & set(stats)


def test_train_step_program_is_named_on_purpose(tiny):
    from paddle_tpu import jit, optimizer
    assert jit.TRAIN_STEP_PROGRAM == "jit_step"
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=tiny.parameters())
    step = jit.TrainStep(tiny, lambda m, b: m(b[0], b[1])[0], opt)
    ids = paddle.to_tensor(np.zeros((1, 8), np.int32))
    assert _module_name(step.lower((ids, ids))) == jit.TRAIN_STEP_PROGRAM


# -- named scopes are metadata only -----------------------------------------

def test_decode_block_span_counter_names(tiny):
    """The counters the ``serving.decode_block`` span carries, by the
    names PERF.md's rows give them; the same are attributes of the
    engine beside ``steps`` / ``slot_steps`` (the span itself is checked
    on a served stream in tests/test_serving_paged.py and
    tests/test_slot_sampler.py). A fresh engine's slots are all dead at
    pos 0: one page a slot a step, and nothing samples."""
    from paddle_tpu.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(tiny, num_slots=2, max_len=64,
                                   decode_block=4, paged=True,
                                   block_size=8, prefill_chunk=8)
    assert eng._decode_block_counters() \
        == {"sampled_steps": 0, "kv_pages_live": 8, "kv_pages_copied": 8,
            "arm_ns": 0}
    assert (eng.sampled_steps, eng.kv_pages_live, eng.kv_pages_copied) \
        == (0, 8, 8)
    dense = ContinuousBatchingEngine(tiny, num_slots=2, max_len=64,
                                     decode_block=4)
    assert dense._decode_block_counters() == {"sampled_steps": 0}


def test_admit_span_counts_the_blocks_it_took_by_eviction(tiny):
    """``serving.admit`` carries ``fresh_blocks`` (allocated by this
    admission), ``hashed_blocks`` (block digests this admission computed)
    and ``evicted_blocks`` (of the fresh ones, taken by evicting a
    retained prefix block), and ``Server.stats()["block_evictions"]`` is
    the engine's total: 0 until the arena has turned over."""
    import time

    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    eng = ContinuousBatchingEngine(tiny, num_slots=2, max_len=64,
                                   decode_block=4, paged=True,
                                   block_size=8, prefill_chunk=8,
                                   num_blocks=11)
    srv = Server(eng, Scheduler())
    rs = np.random.RandomState(0)
    rids = [srv.submit(rs.randint(0, tiny.config.vocab_size, (19,))
                       .astype(np.int32), max_new_tokens=6)
            for _ in range(6)]
    t0 = time.perf_counter()
    srv.run_until_idle()
    admits = [sp for sp in tracing.since(t0) if sp.name == "serving.admit"]
    assert [sp.ids["rid"] for sp in admits] == rids
    assert all(set(sp.ids) == {"rid", "fresh_blocks", "evicted_blocks",
                               "hashed_blocks", "reserved_ns", "keyed_ns"}
               for sp in admits)
    # a cold lookup hashes the one block it misses on; a life, every
    # block it wrote: (19 + 6 - 1) // 8
    assert [sp.ids["hashed_blocks"] for sp in admits] == [1] * 6
    assert srv.stats()["hashed_blocks"] == eng.manager.hashed_blocks == 18
    # distinct prompts: nothing shared, three blocks each
    assert [sp.ids["fresh_blocks"] for sp in admits] == [3] * 6
    evicted = [sp.ids["evicted_blocks"] for sp in admits]
    assert evicted[:3] == [0, 0, 0] and evicted[-1] > 0
    assert all(e <= 3 for e in evicted)
    assert sum(evicted) == eng.manager.evictions \
        == srv.stats()["block_evictions"] > 0
    eng.manager.assert_consistent()


def test_starvation_sync_and_stall_names(tiny):
    """What PR 35 put on the spans a tick already had, by the names
    ``benchmark/window_spans.py`` reads and PERF.md section 3 lists:
    ``starved_ns`` on the two enqueue spans, ``arm_ns`` on
    ``serving.decode_block``, ``first_ns`` / ``fetches`` on
    ``serving.decode_sync`` (the speculative engine's too), the engine's
    counters and the four ``Server.stats()`` keys."""
    import time

    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import (ContinuousBatchingEngine, Scheduler,
                                    Server)
    from paddle_tpu.serving.spec import SpecConfig

    def served(**kw):
        eng = ContinuousBatchingEngine(tiny, num_slots=2, max_len=64,
                                       decode_block=4, paged=True,
                                       block_size=8, prefill_chunk=8, **kw)
        srv = Server(eng, Scheduler())
        # the third refills a slot beside a live stream: its first chunk
        # is enqueued on a drained device
        for n, new in ((19, 5), (9, 13), (12, 5)):
            srv.submit(np.arange(n, dtype=np.int32), max_new_tokens=new)
        t0 = time.perf_counter()
        srv.run_until_idle()
        by = {}
        for sp in tracing.since(t0):
            by.setdefault(sp.name, []).append(sp)
        return eng, srv, by

    eng, srv, by = served()
    assert all(set(sp.ids) == {"first_ns", "fetches"}
               for sp in by["serving.decode_sync"])
    assert all("arm_ns" in sp.ids for sp in by["serving.decode_block"])
    starved = [sp for sps in by.values() for sp in sps
               if "starved_ns" in sp.ids]
    assert {sp.name for sp in starved} == {"serving.prefill_chunk",
                                           "serving.decode_block"}
    assert (eng.device_starved_ns, eng.sync_stalls, eng.sync_stall_ns) \
        == (sum(sp.ids["starved_ns"] for sp in starved), 0, 0)
    stats = srv.stats()
    assert {"device_starved_s", "device_starved_share", "sync_stalls",
            "sync_stall_s"} <= set(stats)
    assert (stats["sync_stalls"], stats["sync_stall_s"]) == (0, 0.0)

    eng, srv, by = served(spec=SpecConfig(k=3))
    assert all(set(sp.ids) == {"first_ns", "fetches"}
               and sp.ids["fetches"] == 1
               for sp in by["serving.decode_sync"])
    assert eng.device_starved_ns == sum(
        sp.ids["starved_ns"] for sp in by["serving.spec_verify"]
        + by["serving.prefill_chunk"] if "starved_ns" in sp.ids) > 0


def _op_histogram(text: str) -> dict:
    ops = re.findall(r"= \"?([a-z_]+\.[a-z_.]+)\"?[ (<]", text)
    return {op: ops.count(op) for op in set(ops)}


def test_named_scopes_leave_the_programs_unchanged(tiny, monkeypatch):
    """``attn`` / ``mlp`` / ``lm_head`` / ``sample`` show in the lowered
    text's locations and nowhere else: operation for operation the decode
    block, the prefill chunk and the train step are the programs they are
    without the scopes."""
    from paddle_tpu import jit, optimizer

    def lower_all():
        be = _paged_backend(tiny)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=tiny.parameters())
        step = jit.TrainStep(tiny, lambda m, b: m(b[0], b[1])[0], opt)
        ids = paddle.to_tensor(np.zeros((1, 8), np.int32))
        return [_lower_block(be), _lower_chunk(be), step.lower((ids, ids))]

    scoped = lower_all()
    with_debug = [low.as_text(debug_info=True) for low in scoped]
    for scope in ("attn", "mlp", "lm_head", "sample"):
        assert f"{scope}/" in with_debug[0], scope     # the decode block
    for scope in ("attn", "mlp", "lm_head"):           # the train step
        assert f"jvp({scope})/" in with_debug[2], scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower_all()
    assert "attn/" not in bare[0].as_text(debug_info=True)
    for a, b in zip(scoped, bare):
        ha, hb = _op_histogram(a.as_text()), _op_histogram(b.as_text())
        assert ha == hb and sum(ha.values()) > 50
