"""Compile-only rehearsal for the v5e: every Pallas kernel the two main
paths (paged serving, training) dispatch on a TPU, at Llama-2-7B widths
(hidden 4096, 32 heads x 128, ff 11008), compiled for a DESCRIBED
``v5e:2x2`` topology — no chip attached, nothing runs. This is what
catches what interpret mode cannot: a block too large for the chip's
16 MiB scoped VMEM, a slice not aligned to the tiling.

A compile that passes is not a chip run; ``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture (only the
xdist worker that is handed this file loads the TPU compiler), the
backend gates are steered from here (never through a program option,
never via interpret mode), and the persistent compile cache is off
around the file: an entry compiled for a described chip cannot be read
back without one.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused
from paddle_tpu.ops.pallas import paged_attention as pa

# Llama-2-7B widths (models/llama.py llama_7b_config)
HIDDEN, HEADS, HEAD_DIM, FF = 4096, 32, 128, 11008
SLOTS, MAX_LEN, KV_BLOCK = 8, 2048, 16
MAX_BLOCKS = MAX_LEN // KV_BLOCK                 # 128-entry table
NUM_BLOCKS = 1 + SLOTS * MAX_BLOCKS
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture
def on_tpu_gates(monkeypatch):
    """Answer the one ``jax.default_backend() == "tpu"`` question every
    dispatch gate asks: the process sees the CPU, the program is compiled
    for the chip. Everything else the gates look at stays live — except a
    multi-device current mesh that an EARLIER test file on this xdist
    worker left set: it closes ``fused.pallas_gate()`` and every case here
    would error in this fixture (which file runs before this one depends
    on timing), so the current mesh is cleared for the test and put back
    after it."""
    from paddle_tpu.distributed import mesh as pmesh
    left_over = pmesh.get_current_mesh()
    pmesh.set_current_mesh(None)
    monkeypatch.setattr(fused, "_on_tpu", lambda: True)
    assert not fused._FORCE_INTERPRET and not fa._FORCE_INTERPRET
    assert fused._pallas_ok() and fa._pallas_available()
    yield
    pmesh.set_current_mesh(left_over)


def _compile(fn, one_chip, *specs):
    """Compile ``fn`` for the described chip; returns the program text
    (raises whatever the chip's compiler would raise)."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


# the paged read's two shapes: Llama-2-7B's (equal heads) and the one the
# benchmark's mistral7b-decode-sat cell serves (32 slots, a 256-entry
# table, 8 KV heads under 32 query heads, 5,121 blocks)
PAGED_SHAPES = {
    "llama7b": dict(slots=SLOTS, kv_heads=HEADS, max_blocks=MAX_BLOCKS,
                    num_blocks=NUM_BLOCKS),
    "served": dict(slots=32, kv_heads=8, max_blocks=256, num_blocks=5121),
}


def _paged_specs(arena_dtype, slots, kv_heads, max_blocks, num_blocks):
    q = ((slots, HEADS, HEAD_DIM), BF16)
    arena = ((num_blocks, KV_BLOCK, kv_heads, HEAD_DIM), arena_dtype)
    table = ((slots, max_blocks), jnp.int32)
    lens = ((slots,), jnp.int32)
    return q, arena, table, lens


SCALE = 1.0 / np.sqrt(HEAD_DIM)


def _walk_fits_vmem(max_blocks, *arenas):
    """The walk's own account of its VMEM scratch is under Mosaic's
    default scoped limit (the compile below is the chip's word on it)."""
    pages = [(pa._page_view(shape)[1:], dtype) for shape, dtype in arenas]
    ppc = pa._pages_per_chunk(max_blocks, pages)
    assert pa._tiles(*arenas[0])
    assert pa._walk_vmem_bytes(ppc, pages) <= pa._VMEM_BUDGET \
        < pa._SCOPED_VMEM_LIMIT


def _arenas_are_viewed_not_copied(text):
    """The kernel's (bs * kvh, d) view of a K / V page is a bitcast: a
    copy here would re-lay the whole arena out on every call."""
    assert not re.search(r"= (bf16|s8)\[\d+,\d+,\d+\]\S* copy\(", text)
    return text


def _case_paged_bf16(one_chip, shape="llama7b"):
    q, arena, table, lens = _paged_specs(BF16, **PAGED_SHAPES[shape])
    _walk_fits_vmem(table[0][1], arena, arena)
    return _arenas_are_viewed_not_copied(_compile(
        functools.partial(pa.paged_attention_decode, scale=SCALE),
        one_chip, q, arena, arena, table, lens))


def _case_paged_int8(one_chip, shape="llama7b"):
    q, arena, table, lens = _paged_specs(jnp.int8, **PAGED_SHAPES[shape])
    scales = (arena[0][:-1], jnp.float32)
    _walk_fits_vmem(table[0][1], arena, arena, scales, scales)
    return _arenas_are_viewed_not_copied(_compile(
        functools.partial(pa.paged_attention_decode_int8, scale=SCALE),
        one_chip, q, arena, arena, scales, scales, table, lens))


def _case_latent_decode(one_chip):
    """The latent (MLA, absorbed) decode read at the shape the benchmark's
    kanana2-docqa-decode cell serves: 64 slots, 32 heads against ONE
    shared row a token (512 + 64 values, stored 640 wide), a 320-entry
    table, 16,385 blocks; the value is the row's first 512 columns."""
    arena = ((16385, KV_BLOCK, 640), BF16)
    pages = [(arena[0][1:], BF16)]
    assert pa._tiles(*arena)
    assert pa._walk_vmem_bytes(pa._pages_per_chunk(320, pages), pages) \
        <= pa._VMEM_BUDGET
    text = _arenas_are_viewed_not_copied(_compile(
        functools.partial(pa.mla_paged_attention_decode,
                          scale=192 ** -0.5, rank=512),
        one_chip, ((64, HEADS, 640), BF16), arena, ((64, 320), jnp.int32),
        ((64,), jnp.int32)))
    assert "%mla_paged_attention_decode" in text
    return text


def _case_packed_decode(one_chip, window=None):
    """The packed reads at the shapes the benchmark's mimo-v2-agent-decode
    cell serves: 64 slots, 64 query heads, a 512-entry table, K 192 and V
    128 in one 384-wide row. A full layer: 4 kv heads, 28,673 blocks, the
    walk under ``paged_attention_decode``; a window layer: 8 kv heads,
    3,201 blocks, ``swa_paged_attention_decode`` with a per-head sink,
    whose chunk holds the nine pages a window of 128 can span."""
    kvh, blocks = (4, 28673) if window is None else (8, 3201)
    arena = ((blocks, KV_BLOCK * kvh, 384), BF16)
    pages = [(arena[0][1:], BF16)]
    assert pa._tiles(*arena)
    assert pa._walk_vmem_bytes(
        pa._pages_per_chunk(512 if window is None else 9, pages), pages) \
        <= pa._VMEM_BUDGET
    specs = [((64, 64, 384), BF16), arena, ((64, 512), jnp.int32),
             ((64,), jnp.int32)]
    kw = dict(scale=192 ** -0.5, kvh=kvh, dv=128)
    if window is None:
        fn, name = functools.partial(pa.packed_paged_attention_decode,
                                     **kw), "%paged_attention_decode"
    else:
        fn = functools.partial(pa.swa_paged_attention_decode, window=window,
                               **kw)
        name = "%swa_paged_attention_decode"
        specs.append(((64,), jnp.float32))
    text = _arenas_are_viewed_not_copied(_compile(fn, one_chip, *specs))
    assert name in text
    return text


_QKV = ((2, 2048, HEADS, HEAD_DIM), BF16)


def _case_sdpa_train(one_chip):
    """The route an equal-heads bf16 model takes in training: forward
    and backward of causal sdpa at s=2048."""
    def loss(q, k, v):
        return fa.sdpa(q, k, v, is_causal=True).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    _QKV, _QKV, _QKV)
    assert fa.sdpa_last_dispatch() == "jax_flash"
    return text


def _case_flash_fused(one_chip):
    return _compile(
        lambda q, k, v: fa.flash_attention_fused(q, k, v, is_causal=True),
        one_chip, _QKV, _QKV, _QKV)


_ROWS = ((2, 2048, HIDDEN), BF16)
_W = ((HIDDEN,), BF16)


def _case_rms(one_chip):
    return _compile(lambda x, w: fused.fused_rms_norm(x, w, 1e-5),
                    one_chip, _ROWS, _W)


def _case_rms_decode_rows(one_chip):
    """The decode block's shape: one row per slot."""
    return _compile(lambda x, w: fused.fused_rms_norm(x, w, 1e-5),
                    one_chip, ((SLOTS, 1, HIDDEN), BF16), _W)


def _case_rms_residual(one_chip):
    return _compile(
        lambda x, r, w: fused.fused_rms_norm(x, w, 1e-5, residual=r),
        one_chip, _ROWS, _ROWS, _W)


def _case_rope(one_chip):
    cs = ((2048, HEAD_DIM), BF16)
    return _compile(fused.fused_rope, one_chip, _QKV, _QKV, cs, cs)


def _case_adamw(one_chip):
    """One 4096 x 11008 leaf in the bf16 params + bf16 moments setting
    chip_smoke.py trains in; the moments must keep their dtype."""
    leaf = ((HIDDEN, FF), BF16)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (leaf,) * 4]
    lowered = jax.jit(lambda p, g, m, v: fused.fused_adamw(
        p, g, m, v, 1e-4, weight_decay=0.01, step=3)).lower(*args)
    assert [o.dtype for o in jax.tree.leaves(lowered.out_info)] \
        == [BF16] * 3
    return lowered.compile().as_text()


CASES = {
    "paged_decode_bf16": _case_paged_bf16,
    "paged_decode_int8": _case_paged_int8,
    "paged_decode_bf16_served_shape":
        functools.partial(_case_paged_bf16, shape="served"),
    "paged_decode_int8_served_shape":
        functools.partial(_case_paged_int8, shape="served"),
    "latent_decode_served_shape": _case_latent_decode,
    "packed_decode_served_shape": _case_packed_decode,
    "swa_packed_decode_served_shape":
        functools.partial(_case_packed_decode, window=128),
    "sdpa_jax_flash_fwd_bwd": _case_sdpa_train,
    "flash_attention_fused_fwd": _case_flash_fused,
    "rms_norm": _case_rms,
    "rms_norm_decode_rows": _case_rms_decode_rows,
    "rms_norm_residual": _case_rms_residual,
    "rope": _case_rope,
    "adamw_leaf": _case_adamw,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, on_tpu_gates):
    text = CASES[case](one_chip)
    assert "tpu_custom_call" in text, f"{case}: no Pallas call in the program"


def abstract_paged_decode_program(layers, one_chip, kv_int8=False):
    """Lower the paged engine's ONE decode-block program at full width
    without a weight in memory: the model is built abstractly
    (utils/scale.abstract_init), the backend's own jitted block is
    lowered on ``jax.eval_shape``-style specs placed on ``one_chip``."""
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_7b_config
    from paddle_tpu.serving.paging import PagedModelStepBackend
    from paddle_tpu.utils.scale import abstract_init

    cfg = llama_7b_config(num_hidden_layers=layers, dtype="bfloat16",
                          tensor_parallel=False)
    with abstract_init("bfloat16"):
        model = LlamaForCausalLM(cfg)
    backend = PagedModelStepBackend(
        model, SLOTS, MAX_LEN, decode_block=8, block_size=KV_BLOCK,
        num_blocks=NUM_BLOCKS, kv_int8=kv_int8, prefill_chunk=128)

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    cache = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                  for shape, dtype in backend.pool_specs)
    return backend._block_jit.lower(
        [spec(v) for v in backend._pv], [spec(v) for v in backend._bv],
        cache, jax.tree.map(spec, backend.init_state()))


def hbm_bytes(compiled) -> int:
    """Device bytes one program holds, from the compiler's own account
    (donated arguments alias their outputs and count once)."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def abstract_train_step(layers, mesh_or_chip, batch=2, seq=2048,
                        tensor_parallel=False):
    """Lower one whole ``TrainStep`` (forward, backward, AdamW in the bf16
    params + moments setting) at full width on abstract weights placed by
    ``mesh_or_chip``: a one-device sharding, or a mesh the model's own
    partition specs are attached for."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_7b_config
    from paddle_tpu.utils.scale import (abstract_init, abstract_state_specs,
                                        attach_shardings)

    cfg = llama_7b_config(
        num_hidden_layers=layers, dtype="bfloat16", recompute=True,
        scan_layers=True, tensor_parallel=tensor_parallel,
        max_position_embeddings=seq)
    with abstract_init("bfloat16"):
        model = LlamaForCausalLM(cfg)
    if isinstance(mesh_or_chip, Mesh):
        attach_shardings(model, mesh_or_chip)
        replicated = NamedSharding(mesh_or_chip, P())
        batch_sharding = NamedSharding(mesh_or_chip, P("dp", None))
    else:
        replicated = batch_sharding = mesh_or_chip
        for _, p in model.named_parameters():
            p._value = jax.ShapeDtypeStruct(
                p._value.shape, p._value.dtype, sharding=replicated)
    for _, b in model.named_buffers():
        b._value = jax.ShapeDtypeStruct(b._value.shape, b._value.dtype,
                                        sharding=replicated)
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters(),
                          multi_precision=False)
    step = TrainStep(model, lambda m, b: m(b[0], b[1])[0], opt)
    step._build()
    opt._slots = abstract_state_specs(
        opt.functional_state(),
        {n: t._value for n, t in step._ptensors.items()})["slots"]
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=batch_sharding)
    return step.lower((ids, ids))


def test_train_step_compiles_for_v5e(one_chip, on_tpu_gates):
    """The whole one-chip train step at 2 layers: attention on jax's
    flash kernel, every fused kernel present, inside the chip's HBM."""
    compiled = abstract_train_step(2, one_chip).compile()
    assert fa.sdpa_last_dispatch() == "jax_flash"
    assert "tpu_custom_call" in compiled.as_text()
    assert hbm_bytes(compiled) < 12 * 2 ** 30


def test_mesh_train_step_takes_the_jnp_routes(topo, on_tpu_gates):
    """jax refuses a Mosaic kernel in a program GSPMD partitions, so under
    a multi-device current mesh the gate closes outside shard_map (and
    stays open inside a fully-manual one): the dp=2 x mp=2 train step
    compiles for the four chips on the jnp routes, weights still split."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import mesh as pmesh

    hcg = pmesh.HybridCommunicateGroup(dp_degree=2, mp_degree=2,
                                       devices=list(topo.devices))
    try:
        assert not fused.pallas_gate()
        inside = []
        jax.eval_shape(jax.shard_map(
            lambda x: (inside.append(fused.pallas_gate()), x)[1],
            mesh=hcg.jax_mesh, in_specs=P("mp"), out_specs=P("mp"),
            check_vma=False), jax.ShapeDtypeStruct((8,), jnp.float32))
        assert inside == [True]
        compiled = abstract_train_step(2, hcg.jax_mesh,
                                       tensor_parallel=True).compile()
    finally:
        pmesh.set_current_mesh(None)
    assert fa.sdpa_last_dispatch() == "xla"
    assert "tpu_custom_call" not in compiled.as_text()
    specs = {str(s.spec) for s in jax.tree.leaves(
        compiled.output_shardings[1])}
    assert any("'mp'" in s for s in specs), specs


def test_paged_decode_block_compiles_for_v5e(one_chip, on_tpu_gates):
    """The engine's ONE decode program (lax.scan of the shared step over
    the decode block) at full width and 2 layers holds the Pallas paged
    read and fits the chip."""
    compiled = abstract_paged_decode_program(2, one_chip).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's own name on the instruction, as the device trace will
    # show it (the benchmark's breakdown reads ``paged_attention_decode.<n>``
    # where it read ``closed_call.<n>`` before the kernels had names)
    assert "%paged_attention_decode." in text
    assert "%closed_call" not in text
    assert hbm_bytes(compiled) < 12 * 2 ** 30


def abstract_looped_programs(layers, one_chip, slots=8):
    """The paged engine's two programs for an Ouro-2.6B-class looped model
    at full width and ``layers`` layers, lowered on abstract weights as
    ``abstract_paged_decode_program`` lowers the dense model's: the cell's
    deployment (``slots`` slots of 1,024 positions, 1 + 40 x slots blocks, a
    256-token chunk). Returns ``(block, chunk, arena shape)``."""
    from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
    from paddle_tpu.serving.paging import PagedModelStepBackend
    from paddle_tpu.utils.scale import abstract_init

    with abstract_init("bfloat16"):
        model = OuroForCausalLM(OuroConfig(num_hidden_layers=layers,
                                           dtype="bfloat16"))
    num_blocks = 1 + 40 * slots
    be = PagedModelStepBackend(
        model, slots, 1024, decode_block=8, block_size=KV_BLOCK,
        num_blocks=num_blocks, kv_int8=False, prefill_chunk=256)

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def scalar(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                  for shape, dtype in be.pool_specs)
    pv, bv = [spec(v) for v in be._pv], [spec(v) for v in be._bv]
    block = be._block_jit.lower(pv, bv, cache,
                                jax.tree.map(spec, be.init_state()))
    chunk = be._chunk_jit.lower(
        pv, bv, scalar(jnp.int32, 1, 256), cache,
        scalar(jnp.int32, 1, be.max_blocks), scalar(jnp.int32),
        scalar(jnp.int32), scalar(jnp.uint32, 2), scalar(jnp.float32),
        scalar(jnp.int32), scalar(jnp.float32))
    return block, chunk, be.pool_specs[0][0]


@pytest.mark.parametrize("program", ["block", "chunk"])
def test_looped_programs_hold_each_layer_once_and_copy_no_arena(
        program, one_chip, on_tpu_gates):
    """A looped model's decode block and chunk program at full width and 2
    layers of 4 passes each: the passes are a loop in the program, so the
    compiled decode block calls ``paged_attention_decode`` at 2 sites, not
    8; no instruction copies an arena (a weight layer's ``(4 x 321, 16, 16,
    128)`` pair is carried through the loop and scattered into in place);
    and what the program holds beyond its arguments, less the q / k / v
    weights the compiler re-lays-out once before the loops (8 MiB each), is
    under one arena."""
    block, chunk, arena = abstract_looped_programs(2, one_chip)
    assert arena == (4 * 321, KV_BLOCK, 16, 128)
    compiled = (block if program == "block" else chunk).compile()
    text = compiled.as_text()
    sites = len(set(re.findall(r"%(paged_attention_decode\.\d+) = ", text)))
    assert sites == (2 if program == "block" else 0)
    shape = "bf16[%s]" % ",".join(map(str, arena))
    made = [line for line in text.splitlines()
            if re.search(r"= %s\S* (copy|dynamic-update-slice|"
                         r"dynamic-slice|concatenate|pad)\(" % re.escape(shape),
                         line)]
    assert not made, made[:2]
    ma = compiled.memory_analysis()
    arena_bytes = int(np.prod(arena)) * 2
    assert ma.alias_size_in_bytes >= 4 * arena_bytes      # donated in place
    relaid = 2 * 3 * 2048 * 2048 * 2
    assert ma.temp_size_in_bytes - relaid < arena_bytes
    assert hbm_bytes(compiled) < 2 * 2 ** 30


# -- learned sparse attention over a latent cache (dots3-longdoc-decode) ------

DSA = dict(slots=64, mb=2304, blocks=49153, window_blocks=6401)


def _case_dsa_index(one_chip):
    """The indexer's decode walk at the shape dots3-longdoc-decode serves:
    64 slots, 64 index heads of 128 against one 128-wide key a token, a
    2,304-entry table, 49,153 blocks; scores out for all 36,864 positions."""
    arena = ((DSA["blocks"], KV_BLOCK, 128), BF16)
    assert pa._tiles(*arena)
    text = _arenas_are_viewed_not_copied(_compile(
        pa.dsa_index_scores_decode, one_chip, ((64, 64, 128), BF16),
        ((64, 64), jnp.float32), arena, ((64, DSA["mb"]), jnp.int32),
        ((64,), jnp.int32)))
    assert "%dsa_index_scores_decode" in text
    return text


def _case_dsa_sparse(one_chip):
    """The selected read: 2,048 row ids a slot gathered from the 640-wide
    latent arena (never the table), 128 heads over them in one call."""
    arena = ((DSA["blocks"], KV_BLOCK, 640), BF16)
    text = _compile(
        functools.partial(pa.dsa_sparse_mla_decode, scale=192 ** -0.5,
                          rank=512),
        one_chip, ((64, 128, 640), BF16), arena,
        ((64, DSA["mb"]), jnp.int32), ((64, 2048), jnp.int32),
        ((64,), jnp.int32))
    assert "%dsa_sparse_mla_decode" in text
    # nothing table-sized (64 x 36,864 rows) or arena-sized is made
    assert not re.search(r"bf16\[64,36864,640\]|bf16\[49153,16,640\]\S* copy",
                         text)
    return text


def _case_swa_mla(one_chip):
    """The sliding layers' latent read: 64 heads against a 1,152-wide row
    (rank 1024), a window of 513 over a ring, 6,401 blocks."""
    arena = ((DSA["window_blocks"], KV_BLOCK, 1152), BF16)
    pages = [(arena[0][1:], BF16)]
    assert pa._tiles(*arena)
    assert pa._walk_vmem_bytes(pa._pages_per_chunk(33, pages), pages) \
        <= pa._VMEM_BUDGET
    text = _arenas_are_viewed_not_copied(_compile(
        functools.partial(pa.swa_mla_paged_attention_decode,
                          scale=256 ** -0.5, rank=1024, window=513),
        one_chip, ((64, 64, 1152), BF16), arena,
        ((64, DSA["mb"]), jnp.int32), ((64,), jnp.int32)))
    assert "%swa_mla_paged_attention_decode" in text
    return text


CASES.update({"dsa_index_scores_served_shape": _case_dsa_index,
              "dsa_sparse_read_served_shape": _case_dsa_sparse,
              "swa_mla_decode_served_shape": _case_swa_mla})


def abstract_sparse_latent_programs(one_chip, layers=5):
    """The hybrid engine's two programs for the dots3-note-prev
    configuration as the benchmark serves it (published widths, 16 of 256
    experts, 64 slots of 36,864, both pools), lowered on abstract weights."""
    import json
    from benchmark import weights_by_class
    from paddle_tpu.models.dots3_note import Dots3NoteForCausalLM
    from paddle_tpu.serving.hybrid import HybridPagedStepBackend
    from paddle_tpu.utils.scale import abstract_init
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        c = json.load(f)
    cfg = weights_by_class.model_config(
        c, n_routed_experts=256, experts_held=(0, 16),
        num_hidden_layers=layers)
    with abstract_init("bfloat16"):
        model = Dots3NoteForCausalLM(cfg)
    dep = c["deployment"]
    be = HybridPagedStepBackend(
        model, dep["num_slots"], dep["max_len"], 8, KV_BLOCK,
        dep["num_blocks"], dep["window_blocks"], 512)

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def scalar(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                  for shape, dtype in be.pool_specs)
    pv, bv = [spec(v) for v in be._pv], [spec(v) for v in be._bv]
    block = be._block_jit.lower(pv, bv, cache,
                                jax.tree.map(spec, be.init_state()))
    chunk = be._chunk_jit.lower(
        pv, bv, scalar(jnp.int32, 1, 512), cache,
        scalar(jnp.int32, 1, be.table_width), scalar(jnp.int32),
        scalar(jnp.int32), scalar(jnp.uint32, 2), scalar(jnp.float32),
        scalar(jnp.int32), scalar(jnp.float32))
    return block, chunk, be


@pytest.mark.parametrize("program", ["block", "chunk"])
def test_sparse_latent_programs_fit_the_chip_and_copy_no_arena(
        program, one_chip, on_tpu_gates):
    """dots3-longdoc-decode's two programs at the served size: weights +
    both pools + the program's temporaries fit the chip; the decode block
    holds the three named Pallas calls (2 + 2 + 3 sites) and makes nothing
    table-sized (64 x 36,864 rows of a latent or a key) or arena-sized; the
    chunk program takes no fp32 scores over the whole table."""
    block, chunk, be = abstract_sparse_latent_programs(one_chip)
    assert be.leaf_group == (0, 0, 0, 0, 1, 1, 1, None)
    compiled = (block if program == "block" else chunk).compile()
    text = compiled.as_text()
    assert hbm_bytes(compiled) < 14 * 2 ** 30, hbm_bytes(compiled) / 2 ** 30
    ma = compiled.memory_analysis()
    if program == "block":
        for name, sites in (("dsa_index_scores_decode", 2),
                            ("dsa_sparse_mla_decode", 2),
                            ("swa_mla_paged_attention_decode", 3)):
            assert len(set(re.findall(rf"%({name}\.\d+) = ", text))) \
                == sites, name
        # a table's worth of latent rows or index keys, or a copied arena
        assert not re.search(
            r"bf16\[64,36864,(640|128)\]|bf16\[64,2304,16,(640|128)\]|"
            r"bf16\[(49153|6401),16,\d+\]\S* copy\(", text)
        assert ma.temp_size_in_bytes < 1.5 * 2 ** 30
    else:
        assert not re.search(r"f32\[1,128,512,36864\]", text)
        assert ma.temp_size_in_bytes < 3 * 2 ** 30
