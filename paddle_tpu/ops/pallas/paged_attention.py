"""Paged-attention decode kernel over a block-paged KV arena.

The serving engine's paged mode (serving/paging.py) stores every slot's
KV history as fixed-size blocks inside one shared
``(num_blocks, block_size, kv_heads, head_dim)`` arena; a per-slot
block table maps the slot's timeline block j to an arena block id
(vLLM's PagedAttention layout restated under the repo's static-shape
rules — block 0 is the reserved trash block dead slots write into).

TPU-native design: the kernel runs one grid step per (slot, table
entry); the block table and per-slot lengths ride as SCALAR-PREFETCH
operands so the k/v BlockSpec index_map can address the arena block
directly — the gather IS the DMA schedule, no (S, max_len) dense view
ever materializes. Attention over the blocks is an online softmax
(running max / normalizer / accumulator in VMEM scratch, finalized on
the last table entry), with table entries past the slot's length
skipped via ``pl.when``. Off-TPU (and in the CPU quick lane) the SAME
call falls back to :func:`paged_attention_reference` — a gather of the
table into the dense layout followed by exactly the einsum/mask/softmax
sequence of ``models.generation.cached_attention``, which is what keeps
paged greedy streams bit-identical to the dense engine.

int8 KV mode reuses the EQuARX wire-format helpers from
``distributed/collectives/quantized.py``: codes quantized per
(position, kv-head) vector against its absmax (the "bucket" is the
head_dim vector), dequantized to fp32 at read. Single quantization, no
reduce, so the documented bound specializes to
``absmax / 127 / 2`` elementwise (:func:`kv_int8_error_bound` derives
it from ``int8_error_bound`` with n=1 and no phase-2 term).

Bandwidth-true int8 decode (:func:`paged_attention_decode_int8`): the
dequantization happens INSIDE the read, never ahead of it. On TPU the
int8 kernel DMAs code blocks plus their ``(block_size, kv_heads)``
scale blocks through the same scalar-prefetch index_map and dequantizes
each block in registers — HBM sees ~(1 + 4/d)-byte/element traffic, the
actual quantized footprint. Off-TPU the fallback is a ``lax.scan`` over
table entries that gathers ONE block of codes+scales at a time,
dequantizes it, and folds it into the same online softmax — so even the
CPU jaxpr holds no fp32 KV transient beyond a single
``(b, block_size, kvh, d)`` block (asserted by a recursive jaxpr walk
in tests/test_serving_quant.py). The dequant-then-dense formulation
survives only as :func:`paged_attention_int8_reference`, the test
oracle the in-read paths are pinned against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import fused as _fused

__all__ = ["paged_attention_decode", "paged_attention_decode_int8",
           "paged_attention_reference", "paged_attention_int8_reference",
           "paged_gather", "quantize_kv", "dequantize_kv",
           "kv_int8_error_bound"]

_NEG = -1e30

# tests flip this to route the s=1 int8 read through the
# dequant-then-dense oracle instead of the in-read path — the lever the
# production-vs-oracle greedy-stream parity pin uses
_FORCE_INT8_REFERENCE = False


# ---------------------------------------------------------------------------
# int8 KV wire format (EQuARX helpers, head_dim-vector buckets)
# ---------------------------------------------------------------------------

def quantize_kv(x):
    """(..., d) fp32-ish -> ((..., d) int8 codes, (...,) fp32 absmax
    scales): one EQuARX bucket per (position, kv-head) vector."""
    from ...distributed.collectives.quantized import _quantize
    d = x.shape[-1]
    codes, scales = _quantize(x.astype(jnp.float32).reshape(-1), d)
    return (codes.reshape(x.shape),
            scales.reshape(x.shape[:-1]))


def dequantize_kv(codes, scales):
    """Inverse of :func:`quantize_kv` (fp32 out; the ±127 codes
    reproduce ±absmax bit-exactly, so constant vectors round-trip)."""
    from ...distributed.collectives.quantized import _dequantize
    d = codes.shape[-1]
    return _dequantize(codes.reshape(-1, d),
                       scales.reshape(-1)).reshape(codes.shape)


def _deq_block(codes, scales):
    """Register-level EQuARX dequant of ONE block: codes (..., d) int8,
    scales (...,) fp32 -> fp32. THE collectives formula (±127 codes
    reproduce ±absmax bit-exactly), not a restatement — the Pallas
    kernel, the scan fallback and quantize_kv/dequantize_kv can never
    drift apart."""
    from ...distributed.collectives.quantized import _dequantize
    return _dequantize(codes, scales)


def kv_int8_error_bound(absmax):
    """Worst-case elementwise |dequant - fp32| for the int8 KV cache:
    a single quantization (n=1 contributor, no re-quantized phase 2)
    of the documented collectives contract — absmax / 127 / 2."""
    from ...distributed.collectives.quantized import int8_error_bound
    return int8_error_bound(absmax, 1,
                            bucket_absmax_out=jnp.zeros_like(
                                jnp.asarray(absmax, jnp.float32)))


# ---------------------------------------------------------------------------
# reference path: block-table gather + the dense attention sequence
# ---------------------------------------------------------------------------

def paged_gather(arena, block_table):
    """(nb, bs, kvh, d) arena + (b, max_blocks) table -> the slot-dense
    (b, max_blocks*bs, kvh, d) view ordered by timeline position."""
    b, mb = block_table.shape
    g = arena[block_table]                     # (b, mb, bs, kvh, d)
    return g.reshape(b, mb * g.shape[2], *g.shape[3:])


def _dense_attention(q, kd, vd, lengths, *, scale, window=None):
    """The dense einsum/mask/softmax sequence over already-gathered
    (b, T, kvh, d) k/v — bit-identical math to the dense engine. ``q``
    is (b, s, h, d); q_idx = lengths - s + i."""
    b, s, h, d = q.shape
    kvh = kd.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                        kd.astype(jnp.float32)) * scale
    t_idx = jnp.arange(kd.shape[1])
    q_idx = (lengths - s)[:, None] + jnp.arange(s)[None, :]   # (b, s)
    mask = t_idx[None, None, :] <= q_idx[:, :, None]
    if window is not None:
        mask = mask & (t_idx[None, None, :]
                       > q_idx[:, :, None] - int(window))
    scores = jnp.where(mask[:, None, None], scores, jnp.float32(_NEG))
    probs = jax.nn.softmax(scores, axis=-1).astype(vd.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, vd)
    return out.reshape(b, s, h, d).astype(q.dtype)


def paged_attention_reference(q, k_arena, v_arena, block_table, lengths,
                              *, scale, window=None):
    """Gathered-dense oracle: bit-identical math to the dense engine
    (same einsums, same -1e30 mask, same fp32 softmax). ``q`` is
    (b, s, h, d) — s=1 decode or an s-token prefill chunk whose rows
    end at ``lengths`` (q_idx = lengths - s + i)."""
    kd = paged_gather(k_arena, block_table)
    vd = paged_gather(v_arena, block_table)
    return _dense_attention(q, kd, vd, lengths, scale=scale,
                            window=window)


def paged_attention_int8_reference(q, k_codes, v_codes, k_scales,
                                   v_scales, block_table, lengths, *,
                                   scale, window=None):
    """Dequant-then-dense TEST ORACLE for the int8 arena: gather the
    whole table, dequantize into the dense fp32 layout, run the dense
    attention sequence. This is the very transient the in-read paths
    exist to eliminate — it lives on only to pin their numerics."""
    kd = dequantize_kv(paged_gather(k_codes, block_table),
                       paged_gather(k_scales, block_table))
    vd = dequantize_kv(paged_gather(v_codes, block_table),
                       paged_gather(v_scales, block_table))
    return _dense_attention(q, kd, vd, lengths, scale=scale,
                            window=window)


# ---------------------------------------------------------------------------
# Pallas kernel: decode (s=1), block-table scalar prefetch
# ---------------------------------------------------------------------------

def _online_update(q, k, v, j, bs, length, scale, m_ref, l_ref, acc_ref):
    """Fold one fp32 (bs, kvh, d) KV block into the running online
    softmax (max / normalizer / accumulator scratch refs). Shared by
    the fp32 and int8 kernels — the int8 kernel differs ONLY in how k/v
    reach fp32."""
    kvh = k.shape[1]
    h, d = q.shape
    qg = q.reshape(kvh, h // kvh, d)
    s = jnp.einsum("kgd,tkd->kgt", qg, k) * scale   # (kvh, g, bs)
    t = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(t < length, s, _NEG)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.einsum("kgt,tkd->kgd", p, v)
    m_ref[...] = m_new


def _decode_kernel_core(len_ref, q_ref, read_kv, o_ref, m_ref, l_ref,
                        acc_ref, *, bs, scale, nblocks):
    """ONE online-softmax scratch lifecycle (init at j==0, per-block
    fold, finalize at the last table entry) shared by the fp32 and
    int8 kernels — they differ ONLY in ``read_kv``, how the current
    block's k/v reach fp32."""
    from jax.experimental import pallas as pl
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[i]

    @pl.when(j * bs < length)
    def _block():
        k, v = read_kv()
        _online_update(q_ref[0].astype(jnp.float32), k, v,
                       j, bs, length, scale, m_ref, l_ref, acc_ref)

    @pl.when(j == nblocks - 1)
    def _finalize():
        kvh, g, d = acc_ref.shape
        o_ref[0] = (acc_ref[...] / l_ref[...]).reshape(
            kvh * g, d).astype(o_ref.dtype)


def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs, scale, nblocks):
    _decode_kernel_core(
        len_ref, q_ref,
        lambda: (k_ref[0].astype(jnp.float32),
                 v_ref[0].astype(jnp.float32)),
        o_ref, m_ref, l_ref, acc_ref, bs=bs, scale=scale,
        nblocks=nblocks)


def _decode_kernel_int8(tbl_ref, len_ref, q_ref, k_ref, v_ref, sk_ref,
                        sv_ref, o_ref, m_ref, l_ref, acc_ref, *, bs,
                        scale, nblocks):
    """int8 twin of :func:`_decode_kernel`: the k/v blocks arrive as
    int8 codes plus their (bs, kvh) fp32 absmax scale blocks (same
    scalar-prefetch index_map — the scale DMA rides the code DMA), and
    the dequant happens in registers right before the block's einsum.
    HBM traffic per table entry is the quantized footprint."""
    _decode_kernel_core(
        len_ref, q_ref,
        lambda: (_deq_block(k_ref[0], sk_ref[0]),
                 _deq_block(v_ref[0], sv_ref[0])),
        o_ref, m_ref, l_ref, acc_ref, bs=bs, scale=scale,
        nblocks=nblocks)


def _kernel_ok(k_arena) -> bool:
    """Route the s=1 fp32/bf16 read through the Pallas kernel (real TPU
    or forced interpret mode); everything else takes the gathered-dense
    reference path — including the whole CPU quick lane, which is what
    keeps paged streams bit-identical to the dense engine there."""
    return (k_arena.dtype in (jnp.float32, jnp.bfloat16)
            and _fused._pallas_ok())


def _kernel_ok_int8(k_codes) -> bool:
    """The int8 kernel's routing gate: code arenas only, TPU or forced
    interpret mode. Off-TPU the int8 read takes the per-block scan
    fallback (NOT the dense oracle — the no-fp32-KV-transient contract
    holds on every backend)."""
    return k_codes.dtype == jnp.int8 and _fused._pallas_ok()


def _grid_call(name, kernel, in_specs, operands, b, mb, h, d, kvh,
               out_dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d),
                               lambda i, j, tbl, lens: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, h // kvh, 1), jnp.float32),
            pltpu.VMEM((kvh, h // kvh, 1), jnp.float32),
            pltpu.VMEM((kvh, h // kvh, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_fused._FORCE_INTERPRET, name=name,
    )(*operands)


def paged_attention_decode(q, k_arena, v_arena, block_table, lengths,
                           *, scale):
    """One decode step of paged attention: q (b, h, d) against the
    arena through the block table; lengths (b,) = tokens valid per slot
    (the just-written current token included). Online softmax over the
    table entries; entries past the length are skipped, entry 0 (trash)
    is only ever touched by skipped/dead rows."""
    from jax.experimental import pallas as pl

    b, h, d = q.shape
    nb, bs, kvh, _ = k_arena.shape
    mb = block_table.shape[1]
    in_specs = [
        pl.BlockSpec((1, h, d), lambda i, j, tbl, lens: (i, 0, 0)),
        pl.BlockSpec((1, bs, kvh, d),
                     lambda i, j, tbl, lens: (tbl[i, j], 0, 0, 0)),
        pl.BlockSpec((1, bs, kvh, d),
                     lambda i, j, tbl, lens: (tbl[i, j], 0, 0, 0)),
    ]
    return _grid_call(
        "paged_attention_decode",
        functools.partial(_decode_kernel, bs=bs, scale=scale,
                          nblocks=mb),
        in_specs, (block_table, lengths, q, k_arena, v_arena),
        b, mb, h, d, kvh, q.dtype)


def _int8_decode_fallback(q, k_codes, v_codes, k_scales, v_scales,
                          block_table, lengths, *, scale):
    """Off-TPU mirror of the int8 kernel: ``lax.scan`` over table
    entries, gathering and dequantizing ONE (b, bs, kvh, d) block per
    step into the same online softmax. The largest fp32 KV value alive
    at any point is a single block — the dense (b, T, kvh, d) transient
    of the old dequant-then-gather path never exists (jaxpr-walk
    pinned)."""
    b, h, d = q.shape
    nb, bs, kvh, _ = k_codes.shape
    mb = block_table.shape[1]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d).astype(jnp.float32)

    def body(carry, j):
        m, l, acc = carry
        blk = block_table[:, j]                        # (b,)
        k = _deq_block(k_codes[blk], k_scales[blk])    # (b, bs, kvh, d)
        v = _deq_block(v_codes[blk], v_scales[blk])
        s = jnp.einsum("bkgd,btkd->bkgt", qg, k) * scale
        t = j * bs + jnp.arange(bs)
        s = jnp.where(t[None, None, None, :]
                      < lengths[:, None, None, None], s,
                      jnp.float32(_NEG))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bkgt,btkd->bkgd", p, v)
        return (m_new, l, acc), None

    m0 = jnp.full((b, kvh, g, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, 1), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  jnp.arange(mb, dtype=jnp.int32))
    out = (acc / l).reshape(b, h, d)
    return out.astype(q.dtype)


def paged_attention_decode_int8(q, k_codes, v_codes, k_scales, v_scales,
                                block_table, lengths, *, scale):
    """One decode step against the int8 arena with the dequant INSIDE
    the read: the Pallas int8 kernel on TPU/interpret, the per-block
    scan fallback everywhere else. Numerics: identical quantized inputs
    and fp32 accumulation as the dequant-then-dense oracle, reassociated
    by the online softmax — parity is pinned to ~1e-5, and greedy
    engine streams are pinned token-identical to the oracle route."""
    from jax.experimental import pallas as pl

    if _FORCE_INT8_REFERENCE:
        return paged_attention_int8_reference(
            q[:, None], k_codes, v_codes, k_scales, v_scales,
            block_table, lengths, scale=scale)[:, 0]
    if not _kernel_ok_int8(k_codes):
        return _int8_decode_fallback(
            q, k_codes, v_codes, k_scales, v_scales, block_table,
            lengths, scale=scale)
    b, h, d = q.shape
    nb, bs, kvh, _ = k_codes.shape
    mb = block_table.shape[1]
    in_specs = [
        pl.BlockSpec((1, h, d), lambda i, j, tbl, lens: (i, 0, 0)),
        pl.BlockSpec((1, bs, kvh, d),
                     lambda i, j, tbl, lens: (tbl[i, j], 0, 0, 0)),
        pl.BlockSpec((1, bs, kvh, d),
                     lambda i, j, tbl, lens: (tbl[i, j], 0, 0, 0)),
        pl.BlockSpec((1, bs, kvh),
                     lambda i, j, tbl, lens: (tbl[i, j], 0, 0)),
        pl.BlockSpec((1, bs, kvh),
                     lambda i, j, tbl, lens: (tbl[i, j], 0, 0)),
    ]
    return _grid_call(
        "paged_attention_decode_int8",
        functools.partial(_decode_kernel_int8, bs=bs, scale=scale,
                          nblocks=mb),
        in_specs, (block_table, lengths, q, k_codes, v_codes,
                   k_scales, v_scales),
        b, mb, h, d, kvh, q.dtype)
