"""Paged-attention decode kernel over a block-paged KV arena.

The serving engine's paged mode (serving/paging.py) stores every slot's
KV history as fixed-size blocks inside one shared
``(num_blocks, block_size, kv_heads, head_dim)`` arena; a per-slot
block table maps the slot's timeline block j to an arena block id
(vLLM's PagedAttention layout restated under the repo's static-shape
rules — block 0 is the reserved trash block dead slots write into).

TPU-native design: the kernel walks a slot's LIVE pages, not its
table. A grid step is a slot; its body is ONE loop over the slot's
``cdiv(length, block_size)`` pages (clamped to the table's width), P
pages to a chunk, whose trip count is read at run time from the
scalar-prefetched ``lengths`` — so the engine's one compiled decode
program serves every length, and a read costs what its live bytes cost.
The arenas stay in HBM; the kernel copies each live page itself
(``make_async_copy``, the page's arena id read from the scalar-prefetched
block table) into one of two chunk buffers in VMEM, and starts the next
chunk's copies — the next slot's first chunk, at a slot's end — before
it folds the current chunk into the online softmax (running max /
normalizer / accumulator as fp32 loop carries). Pages past the length
are never copied; the tail of the last page is masked in the scores. A
page is used as the ``(block_size * kv_heads, head_dim)`` matrix it is in
memory, all query heads against all of its rows in one product with the
other kv heads' rows masked (:func:`_walk_kernel`). P comes from shapes
the call can see (:func:`_pages_per_chunk`); :func:`walk_counts` mirrors
the loop bound on the host for the engine's ``kv_pages_live`` /
``kv_pages_copied`` counters. No (S, max_len) dense view ever
materializes. Off-TPU (and in the CPU quick lane) the SAME
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
int8 kernel is the same walk: the loop copies each live page's codes and
its ``(block_size, kv_heads)`` scale page, and a row's ``absmax / 127``
step multiplies that row's column of the scores (K) and of the
probabilities (V) — HBM sees ~(1 + 4/d)-byte/element traffic, the actual
quantized footprint, and no chunk-sized fp32 K or V exists even in VMEM.
(The scale arenas are lane-sparse in HBM, 8 floats to a row: XLA re-lays
them out as lane rows ahead of the call, arena-wide — the one part of
the int8 read that does not scale with live KV; ROADMAP D2.) Off-TPU the
fallback is a ``lax.scan`` over
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
import numpy as np

from . import fused as _fused

__all__ = ["paged_attention_decode", "paged_attention_decode_int8",
           "mla_paged_attention_decode", "mla_paged_attention_reference",
           "packed_paged_attention_decode", "swa_paged_attention_decode",
           "packed_paged_attention_reference",
           "swa_mla_paged_attention_decode", "dsa_index_scores_decode",
           "dsa_index_scores_reference", "dsa_sparse_mla_decode",
           "dsa_sparse_mla_reference",
           "paged_attention_reference", "paged_attention_int8_reference",
           "paged_gather", "quantize_kv", "dequantize_kv",
           "kv_int8_error_bound", "walk_counts"]

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
    reproduce ±absmax bit-exactly), not a restatement — the scan
    fallback and quantize_kv/dequantize_kv can never drift
    apart. (The decode walk applies the same ``absmax / 127`` step to
    score and probability columns instead: :func:`_chunk_dequantized`.)"""
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
# Pallas kernel: decode (s=1), a walk over each slot's LIVE pages
# ---------------------------------------------------------------------------

# Rows of K (tokens x kv heads) folded into the online softmax per loop
# trip, and the VMEM the two chunk buffers of every stream may take
# together. Chosen from chip measurements at the served shape (PR 26,
# PERF.md section 6); the pages per chunk follow from shapes the call
# can see (:func:`_pages_per_chunk`), never from an option.
_CHUNK_ROWS = 2048
_VMEM_BUDGET = 4 * 1024 * 1024
_SCOPED_VMEM_LIMIT = 16 * 1024 * 1024      # Mosaic's default on a v5e


def _tile_pad(shape, dtype):
    """Bytes a VMEM buffer of ``shape`` takes once its two minor
    dimensions are padded to the (sublane, lane) tile of ``dtype``."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // item)
    lead = 1
    for n in shape[:-2]:
        lead *= n
    return (lead * _fused._round_up(shape[-2], sub)
            * _fused._round_up(shape[-1], 128) * item)


def _walk_vmem_bytes(ppc, pages):
    """VMEM scratch of one walk: two chunk buffers of ``ppc`` pages for
    every stream; ``pages`` is [(page_shape, dtype)]."""
    return sum(_tile_pad((2, ppc) + tuple(shape), dtype)
               for shape, dtype in pages)


def _page_view(arena_shape):
    """An arena's shape as the kernel views it, a page the matrix it is
    in memory: ``(nb, bs * kvh, d)`` for K and V, one ``(1, bs * kvh)``
    lane row a page for a scale arena ``(nb, bs, kvh)``. (A latent arena
    ``(nb, bs, w)`` is that view already: :func:`mla_paged_attention_decode`
    passes it as it is.)"""
    nb, bs, kvh = arena_shape[:3]
    return (nb, bs * kvh) + tuple(arena_shape[3:]) \
        if len(arena_shape) == 4 else (nb, 1, bs * kvh)


def _pages_per_chunk(mb, pages):
    """P, the pages one loop trip copies and folds: about
    ``_CHUNK_ROWS`` rows of K, inside ``_VMEM_BUDGET``, at most the
    table's width. ``pages[0]`` is the K page ``(bs * kvh, d)``."""
    (page_rows, _), _ = pages[0]
    ppc = max(1, min(_CHUNK_ROWS // page_rows, mb))
    while ppc > 1 and _walk_vmem_bytes(ppc, pages) > _VMEM_BUDGET:
        ppc -= 1
    return ppc


def walk_counts(lengths, mb, bs, ppc=1, window=None):
    """What the decode kernel's walk does for these per-slot
    ``lengths``, mirrored on the host: ``(live_pages, copied_pages,
    chunks)``. A slot's live pages are ``cdiv(min(length, mb * bs),
    bs)``, less, under a ``window``, the pages that lie wholly before its
    last ``window`` tokens; the kernel copies exactly those (at least
    one, so a length of zero still reads a page) in ``cdiv(copied, ppc)``
    loop trips. ``live_pages / copied_pages`` is the share of the
    kernel's KV traffic that is live KV."""
    ln = np.minimum(np.asarray(lengths, np.int64).reshape(-1), mb * bs)
    live = -(-np.maximum(ln, 0) // bs)
    if window is not None:
        live = live - np.maximum(ln - window, 0) // bs
    copied = np.maximum(live, 1)
    return (int(live.sum()), int(copied.sum()),
            int((-(-copied // ppc)).sum()))


def _walk_kernel(tbl_ref, len_ref, q_ref, *refs, n_streams, to_chunk,
                 v_streams, scale, bs, kvh, mb, ppc, window=None,
                 sinks=False):
    """ONE walk shared by the bf16/fp32 and the int8 kernels: a grid
    step is a slot; its body loops over the slot's live pages, ``ppc``
    to a chunk, copying each live page from the HBM arenas into one of
    two chunk buffers (the next chunk's copies are in flight while the
    current chunk is folded into the online softmax). The trip count is
    read from the scalar-prefetched ``lengths``, so one program serves
    every length. The kernels differ ONLY in ``to_chunk``: how a
    buffered chunk becomes the ``(ppc * bs * kvh, d)`` K and V matrices
    (and, for int8, the per-row scales that go with them), and in
    ``v_streams``, the streams whose buffers V is read from. V may be
    narrower than K: it is then K's chunk cut to the output's width (the
    latent arena, whose row is ``[c_kv | k_pe]`` and whose value is
    ``c_kv``; a packed arena, whose row is ``[v | k]``).

    Under a ``window`` a slot's walk starts at the first page that holds
    one of its last ``window`` tokens (the pages before it are never
    copied, and their table columns never read) and the scores of the
    tokens before ``length - window`` are masked. With ``sinks`` the
    first ref after q holds a per-head scalar ``(h, 1)`` that joins every
    softmax as one more column with no value: the online softmax opens
    at ``m = sink, l = 1, acc = 0``.

    A chunk is used whole, as ``(rows, d)`` with row ``t * kvh + k``
    holding token t of kv head k — the arena page as it lies in memory,
    no transpose. Scores of all h query heads against all rows are one
    product; the rows of the other kv heads and the tokens past the
    length are masked, and the masked probabilities give the output
    from one ``p @ V`` (8x the MXU work of a grouped einsum at 8 kv
    heads, and a sixth of its time on the chip: PERF.md section 6).
    Running max, normalizer and accumulator are fp32 loop carries."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if sinks:
        sink_ref, refs = refs[0], refs[1:]
    hbm, o_ref = refs[:n_streams], refs[n_streams]
    bufs = refs[n_streams + 1:2 * n_streams + 1]
    sems, parity = refs[2 * n_streams + 1:]
    i = pl.program_id(0)
    n_slots = pl.num_programs(0)
    h = q_ref.shape[1]
    dv = o_ref.shape[2]
    rows = ppc * bs * kvh

    def first_page(slot):
        """The page a slot's walk starts at (None: page 0, no window)."""
        if window is None:
            return None
        ln = jnp.minimum(len_ref[slot], mb * bs)
        return jnp.maximum(ln - window, 0) // bs

    def pages_of(slot):
        ln = jnp.minimum(len_ref[slot], mb * bs)
        if window is None:
            return jnp.maximum(pl.cdiv(ln, bs), 1)
        return jnp.maximum(pl.cdiv(ln, bs) - first_page(slot), 1)

    def each_live_page(slot, n_pages, c, buf, fn):
        """``fn`` on the copy of every live page of chunk c of slot (a
        run-time count: the slot's last chunk has fewer than ``ppc``);
        returns that count."""
        n_live = jnp.minimum(n_pages - c * ppc, ppc)

        first = first_page(slot)

        def page_copies(p, carry):
            page = tbl_ref[slot, c * ppc + p] if first is None \
                else tbl_ref[slot, first + c * ppc + p]
            for s in range(n_streams):
                fn(pltpu.make_async_copy(
                    hbm[s].at[page], bufs[s].at[buf, p], sems.at[s, buf]))
            return carry

        jax.lax.fori_loop(0, n_live, page_copies, 0)
        return n_live

    def start(slot, n_pages, c, buf):
        """Start chunk c's copies. A page past the slot's last live one
        is never copied; its place in the V buffer (and V's scales) is
        zeroed instead: it meets a probability of exactly 0, but what an
        older chunk left there may be another slot's NaN. (K's place
        needs nothing: its scores are masked, not multiplied.)"""
        n_live = each_live_page(slot, n_pages, c, buf,
                                lambda cp: cp.start())

        def clear(p, carry):
            for vs in v_streams:
                bufs[vs][buf, p] = jnp.zeros(bufs[vs].shape[2:],
                                             bufs[vs].dtype)
            return carry

        jax.lax.fori_loop(n_live, ppc, clear, 0)

    length = jnp.minimum(len_ref[i], mb * bs)
    n_pages = pages_of(i)
    n_chunks = pl.cdiv(n_pages, ppc)

    @pl.when(i == 0)
    def _first():
        parity[0] = 0
        start(i, n_pages, 0, 0)

    base = parity[0]
    q = q_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
    own_head = (col % kvh) == (
        jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0) // (h // kvh))
    tok = col // kvh
    if window is not None:
        tok = tok + first_page(i) * bs

    def fold(c, carry):
        m, l, acc = carry
        buf = (base + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _next_chunk():
            start(i, n_pages, c + 1, 1 - buf)

        @pl.when((c + 1 == n_chunks) & (i + 1 < n_slots))
        def _next_slot():
            nxt = jnp.minimum(i + 1, n_slots - 1)
            start(nxt, pages_of(nxt), 0, 1 - buf)

        each_live_page(i, n_pages, c, buf, lambda cp: cp.wait())
        k, v, k_scale, v_scale = to_chunk(bufs, buf, q.dtype)
        v = v[:, :dv]
        s = jax.lax.dot_general(
            q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if k_scale is not None:
            s = s * k_scale
        seen = own_head & (c * (ppc * bs) + tok < length)
        if window is not None:
            seen = seen & (c * (ppc * bs) + tok >= length - window)
        s = jnp.where(seen, s * scale, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        if v_scale is not None:
            p = p * v_scale
        acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    opens = (sink_ref[...].astype(jnp.float32),
             jnp.ones((h, 1), jnp.float32)) if sinks else \
        (jnp.full((h, 1), _NEG, jnp.float32), jnp.zeros((h, 1), jnp.float32))
    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, fold, opens + (jnp.zeros((h, dv), jnp.float32),))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    parity[0] = (base + n_chunks) % 2


def _chunk_as_stored(bufs, buf, q_dtype):
    """bf16/fp32 arenas: K and V enter the products as stored (in the
    wider of their dtype and q's); no scales."""
    k, v = bufs[0][buf], bufs[1][buf]
    dt = jnp.promote_types(k.dtype, q_dtype)
    d = k.shape[-1]
    return k.reshape(-1, d).astype(dt), v.reshape(-1, d).astype(dt), \
        None, None


def _chunk_dequantized(bufs, buf, q_dtype):
    """int8 arenas: HBM traffic is the quantized footprint. The codes
    enter the products as fp32 (exact) and each row's dequantization
    step ``absmax / 127`` — the chunk's ``(bs, kvh)`` scale pages, copied
    by the same loop as lane rows — multiplies its column of the scores
    (K) and of the probabilities (V): ``q . (c * step) == (q . c) * step``
    up to fp32 rounding, and no chunk-sized fp32 K or V is ever built."""
    from ...distributed.collectives.quantized import _QMAX
    d = bufs[0].shape[-1]

    def steps(scale_buf):               # (ppc, 1, rows) -> (1, ppc * rows)
        return jnp.concatenate(
            [scale_buf[buf, p] for p in range(scale_buf.shape[1])],
            axis=-1) * (1.0 / _QMAX)

    return (bufs[0][buf].reshape(-1, d).astype(jnp.float32),
            bufs[1][buf].reshape(-1, d).astype(jnp.float32),
            steps(bufs[2]), steps(bufs[3]))


def _chunk_latent(bufs, buf, q_dtype):
    """The latent arena: ONE stream, a row ``[c_kv | k_pe | pad]`` shared
    by every query head. K is the whole row; V is its first ``rank``
    columns, which the walk cuts to the output's width."""
    k = bufs[0][buf]
    k = k.reshape(-1, k.shape[-1]).astype(
        jnp.promote_types(k.dtype, q_dtype))
    return k, k, None, None


def _tiles(arena_shape, dtype) -> bool:
    """Shapes the walk's chunks can take on the chip. The kernel reads
    a page as the ``(bs * kvh, d)`` matrix it is in memory: that view is
    free (a bitcast) when the ``(kvh, d)`` planes are whole tiles — a
    head_dim of whole 128-lane rows, kv heads in whole groups of 8
    sublanes — and an int8 arena's scale page must be whole 128-lane
    rows as well. A latent arena ``(nb, bs, w)`` is its own page view:
    it tiles when a page is whole tiles, ``w`` in 128-lane rows and the
    block a whole number of sublane groups (16 rows of bf16). Anything
    else would be re-laid out, arena-wide, on every call. Interpret mode
    takes any shape."""
    if len(arena_shape) == 3:
        _, bs, w = arena_shape
        return (w % 128 == 0
                and bs % (8 * (4 // jnp.dtype(dtype).itemsize)) == 0)
    _, bs, kvh, d = arena_shape
    return (d % 128 == 0 and kvh % 8 == 0
            and (jnp.dtype(dtype) != jnp.int8 or (bs * kvh) % 128 == 0))


def _kernel_ok(k_arena) -> bool:
    """Route the s=1 fp32/bf16 read through the Pallas kernel (real TPU
    or forced interpret mode); everything else takes the gathered-dense
    reference path — including the whole CPU quick lane, which is what
    keeps paged streams bit-identical to the dense engine there — and so
    does a shape whose pages do not tile (:func:`_tiles`)."""
    return (k_arena.dtype in (jnp.float32, jnp.bfloat16)
            and _fused._pallas_ok()
            and (_fused._FORCE_INTERPRET
                 or _tiles(k_arena.shape, k_arena.dtype)))


def _kernel_ok_int8(k_codes) -> bool:
    """The int8 kernel's routing gate: code arenas only, TPU or forced
    interpret mode, pages that tile. Everything else takes the
    per-block scan fallback (NOT the dense oracle — the
    no-fp32-KV-transient contract holds on every backend)."""
    return (k_codes.dtype == jnp.int8 and _fused._pallas_ok()
            and (_fused._FORCE_INTERPRET
                 or _tiles(k_codes.shape, k_codes.dtype)))


def _walk_call(name, to_chunk, q, arenas, block_table, lengths, scale):
    """The walk over ``arenas`` (K, V and, for int8, their scale
    arenas), each viewed page by page (:func:`_page_view`)."""
    kvh = arenas[0].shape[2]
    arenas = tuple(a.reshape(_page_view(a.shape)) for a in arenas)
    return _walk_pages(name, to_chunk, q, arenas, block_table, lengths,
                       scale, kvh=kvh,
                       v_streams=tuple(range(1, len(arenas), 2)),
                       dv=q.shape[-1])


def _walk_pages(name, to_chunk, q, arenas, block_table, lengths, scale, *,
                kvh, v_streams, dv, window=None, sinks=None):
    """The walk over arenas already in their page view; P from their
    shapes (under a ``window``, from the pages a window can span)."""
    mb = block_table.shape[1]
    if window is not None:
        bs = arenas[0].shape[1] // kvh
        mb = min(mb, -(-(window - 1) // bs) + 1)
    ppc = _pages_per_chunk(mb, [(a.shape[1:], a.dtype) for a in arenas])
    return _walk_pallas_call(q, arenas, block_table, lengths, sinks,
                             name=name, to_chunk=to_chunk,
                             v_streams=v_streams, dv=dv, scale=scale,
                             kvh=kvh, ppc=ppc, window=window,
                             interpret=_fused._FORCE_INTERPRET)


@functools.partial(jax.jit, static_argnames=(
    "name", "to_chunk", "v_streams", "dv", "scale", "kvh", "ppc", "window",
    "interpret"))
def _walk_pallas_call(q, arenas, block_table, lengths, sinks=None, *, name,
                      to_chunk, v_streams, dv, scale, kvh, ppc, interpret,
                      window=None):
    """The ``pallas_call``: the arenas stay in HBM, the table and the
    lengths are scalar-prefetched, q and the output are pipelined per
    slot. The grid is sequential: the chunk buffers, their parity and
    the first chunk of the next slot are handed from one slot to the
    next.

    Jitted on its own so that a model's layers, which all make this call
    at one shape, share ONE trace and ONE lowering of the kernel inside
    the decode program (a sixteenth of that part of set-up at 16 layers);
    everything the trace depends on is an argument."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, d = q.shape
    mb = block_table.shape[1]
    bs = arenas[0].shape[1] // kvh
    n = len(arenas)
    # the per-head sink, where there is one: whole in VMEM for every slot
    sink_in = [] if sinks is None else [
        sinks.astype(jnp.float32).reshape(h, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, d), lambda i, tbl, lens: (i, 0, 0))]
        + [pl.BlockSpec((h, 1), lambda i, tbl, lens: (0, 0))
           for _ in sink_in]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * n,
        out_specs=pl.BlockSpec((1, h, dv), lambda i, tbl, lens: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppc) + a.shape[1:], a.dtype)
                        for a in arenas]
        + [pltpu.SemaphoreType.DMA((n, 2)), pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, n_streams=n, to_chunk=to_chunk,
                          v_streams=v_streams, scale=scale, bs=bs, kvh=kvh,
                          mb=mb, ppc=ppc, window=window,
                          sinks=sinks is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(block_table, lengths, q, *sink_in, *arenas)


def paged_attention_decode(q, k_arena, v_arena, block_table, lengths,
                           *, scale):
    """One decode step of paged attention: q (b, h, d) against the
    arena through the block table; lengths (b,) = tokens valid per slot
    (the just-written current token included). Online softmax over the
    slot's live pages only: a length beyond the table is clamped, and
    the trash block 0 is only ever read through a dead slot's zeroed
    table row."""
    return _walk_call("paged_attention_decode", _chunk_as_stored, q,
                      (k_arena, v_arena), block_table, lengths, scale)


def mla_paged_attention_reference(q, arena, block_table, lengths, *,
                                  scale, rank, window=None, q_block=None):
    """Gathered path of the latent read, any ``s``: the table gathered
    into timeline order, every head's ``q (b, s, h, w)`` against the one
    shared row (``w`` the arena's padded width), causal by ``lengths``
    (row i of the ``s`` ends at ``lengths - s + i``), fp32 softmax, the
    value the row's first ``rank`` columns. Under a ``window`` key j is
    seen from query i iff ``0 <= i - j < window`` and only the table
    columns a window can span are gathered (the packed read's rule: the
    table may cycle over a ring). ``q_block`` evaluates the query rows in
    blocks of that many (s a multiple). Returns ``(b, s, h, rank)``."""
    b, s = q.shape[:2]
    if window is None and q_block is None:
        lat = arena[block_table]                       # (b, mb, bs, w)
        lat = lat.reshape(b, -1, lat.shape[-1])
        scores = jnp.einsum("bshw,btw->bhst", q.astype(jnp.float32),
                            lat.astype(jnp.float32)) * scale
        q_idx = (lengths - s)[:, None] + jnp.arange(s)[None, :]  # (b, s)
        mask = jnp.arange(lat.shape[1])[None, None, :] <= q_idx[:, :, None]
        scores = jnp.where(mask[:, None], scores, jnp.float32(_NEG))
        probs = jax.nn.softmax(scores, axis=-1).astype(lat.dtype)
        out = jnp.einsum("bhst,btr->bshr", probs, lat[..., :rank])
        return out.astype(q.dtype)
    bs, mb = arena.shape[1], block_table.shape[1]
    if window is None:
        tbl = block_table
        t_idx = jnp.broadcast_to(jnp.arange(mb * bs)[None], (b, mb * bs))
    else:
        ncols = min(mb, -(-(window - 1 + s) // bs) + 1)
        first = jnp.clip((lengths - s - (window - 1)) // bs, 0, mb - ncols)
        tbl = jnp.take_along_axis(
            block_table, first[:, None] + jnp.arange(ncols)[None], axis=1)
        t_idx = first[:, None] * bs + jnp.arange(ncols * bs)[None]
    lat = arena[tbl].reshape(b, -1, arena.shape[-1])        # (b, T, w)

    def read(qb, q_idx):
        """``qb (b, n, h, w)`` at positions ``q_idx (b, n)``."""
        scores = jnp.einsum("bshw,btw->bhst", qb, lat,
                            preferred_element_type=jnp.float32) * scale
        mask = t_idx[:, None, :] <= q_idx[:, :, None]           # (b, n, T)
        if window is not None:
            mask = mask & (t_idx[:, None, :] > q_idx[:, :, None] - window)
        scores = jnp.where(mask[:, None], scores, jnp.float32(_NEG))
        probs = jax.nn.softmax(scores, axis=-1).astype(lat.dtype)
        return jnp.einsum("bhst,btr->bshr", probs,
                          lat[..., :rank]).astype(q.dtype)

    q_idx = (lengths - s)[:, None] + jnp.arange(s)[None, :]      # (b, s)
    return _in_query_blocks(read, q, q_idx, q_block)


def _in_query_blocks(read, q, q_idx, q_block, *more, valid_len=None):
    """``read(q (b, n, ...), q_idx (b, n), *more (b, n, ...))`` over the
    ``s`` query rows in blocks of ``q_block`` (s a multiple), so that a
    long chunk's scores fit; whole where ``q_block`` is None or covers s.
    With ``valid_len`` (a scalar) a block whose first row is at or past it
    is not read: its output is zeros."""
    b, s = q.shape[:2]
    if q_block is None or s <= q_block:
        return read(q, q_idx, *more)
    nb = s // q_block

    def blocks(x):
        return jnp.moveaxis(x.reshape((b, nb, q_block) + x.shape[2:]), 1, 0)

    def one(a):
        lo, args = a[0], a[1:]
        if valid_len is None:
            return read(*args)
        shape = jax.eval_shape(read, *args)
        return jax.lax.cond(lo < valid_len, lambda: read(*args),
                            lambda: jnp.zeros(shape.shape, shape.dtype))
    out = jax.lax.map(one, (jnp.arange(nb) * q_block,)
                      + tuple(blocks(x) for x in (q, q_idx) + more))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((b, s) + out.shape[3:])


def mla_paged_attention_decode(q, arena, block_table, lengths, *, scale,
                               rank):
    """One decode step of latent (MLA, absorbed) attention: ``q (b, h,
    w)`` holds per head ``[q_nope W_kvb,k^T | q_pe | 0]``, the arena
    ``(nb, bs, w)`` one row ``[c_kv | k_pe | 0]`` a token, shared by all
    heads. The same walk as :func:`paged_attention_decode` over ONE
    stream: K is the whole row, V its first ``rank`` columns. Returns
    ``o_latent (b, h, rank)``; the caller expands it through
    ``W_kvb,v``."""
    return _walk_pages("mla_paged_attention_decode", _chunk_latent, q,
                       (arena,), block_table, lengths, scale, kvh=1,
                       v_streams=(0,), dv=rank)


def packed_paged_attention_reference(q, arena, block_table, lengths, *,
                                     scale, kvh, dv, window=None,
                                     sinks=None, q_block=None):
    """Gathered path of the packed read, any ``s``. The arena ``(nb, bs *
    kvh, w)`` keeps a page as the matrix the walk reads: row ``t * kvh +
    k`` is token t of kv head k, ``[v (dv) | k | 0]``; ``q (b, s, h, w)``
    holds per head ``[0 (dv) | q | 0]``, so one product over the row gives
    ``q . k``. Causal by ``lengths`` (row i of the ``s`` ends at ``lengths
    - s + i``); under a ``window`` key j is seen from query i iff ``0 <= i
    - j < window``, and only the table columns a window can span are
    gathered (``cdiv(window - 1 + s, bs) + 1`` of them, from the column
    of the first query's oldest key), so the read does not grow with the
    table. ``sinks (h,)``: a per-head scalar that joins the softmax as one
    more column and is then dropped. ``q_block`` evaluates the query rows
    in blocks of that many (s a multiple), so that a long chunk against a
    long table fits. Returns ``(b, s, h, dv)``."""
    b, s, h, w = q.shape
    bs = arena.shape[1] // kvh
    mb = block_table.shape[1]
    g = h // kvh
    if window is None:
        tbl = block_table
        t_idx = jnp.broadcast_to(jnp.arange(mb * bs)[None], (b, mb * bs))
    else:
        ncols = min(mb, -(-(window - 1 + s) // bs) + 1)
        first = jnp.clip((lengths - s - (window - 1)) // bs, 0, mb - ncols)
        tbl = jnp.take_along_axis(
            block_table, first[:, None] + jnp.arange(ncols)[None], axis=1)
        t_idx = first[:, None] * bs + jnp.arange(ncols * bs)[None]
    rows = arena[tbl]                              # (b, cols, bs * kvh, w)
    rows = rows.reshape(b, -1, kvh, w)
    vd = rows[..., :dv]
    sink = None if sinks is None else \
        sinks.astype(jnp.float32).reshape(kvh, g)[None, :, :, None, None]

    def read(qb, q_idx):
        """``qb (b, n, h, w)`` at positions ``q_idx (b, n)``."""
        n = qb.shape[1]
        scores = jnp.einsum(
            "bskgw,btkw->bkgst", qb.reshape(b, n, kvh, g, w).astype(
                jnp.float32), rows.astype(jnp.float32)) * scale
        mask = t_idx[:, None, :] <= q_idx[:, :, None]           # (b, n, T)
        if window is not None:
            mask = mask & (t_idx[:, None, :] > q_idx[:, :, None] - window)
        scores = jnp.where(mask[:, None, None], scores, jnp.float32(_NEG))
        if sink is None:
            probs = jax.nn.softmax(scores, axis=-1)
        else:
            m = jnp.maximum(jnp.max(scores, -1, keepdims=True), sink)
            e = jnp.exp(scores - m)
            probs = e / (jnp.sum(e, -1, keepdims=True) + jnp.exp(sink - m))
        out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(vd.dtype), vd)
        return out.reshape(b, n, h, dv).astype(q.dtype)

    q_idx = (lengths - s)[:, None] + jnp.arange(s)[None, :]      # (b, s)
    if q_block is None or s <= q_block:
        return read(q, q_idx)
    nb = s // q_block
    out = jax.lax.map(
        lambda a: read(*a),
        (jnp.moveaxis(q.reshape(b, nb, q_block, h, w), 1, 0),
         jnp.moveaxis(q_idx.reshape(b, nb, q_block), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dv)


def packed_paged_attention_decode(q, arena, block_table, lengths, *, scale,
                                  kvh, dv):
    """One decode step of grouped-query attention over a PACKED arena
    ``(nb, bs * kvh, w)``, a row ``[v (dv) | k | 0]`` a token a kv head (K
    and V of different widths in one page, the page the matrix the walk
    reads whatever the number of kv heads); ``q (b, h, w)`` per head ``[0
    (dv) | q | 0]``. The same walk as :func:`paged_attention_decode`, under
    the same kernel name, over ONE stream: K is the whole row (the zeros
    of q pass over the value columns), V its first ``dv`` columns.
    Returns ``(b, h, dv)``."""
    return _walk_pages("paged_attention_decode", _chunk_latent, q, (arena,),
                       block_table, lengths, scale, kvh=kvh, v_streams=(0,),
                       dv=dv)


def swa_paged_attention_decode(q, arena, block_table, lengths, sinks, *,
                               scale, kvh, dv, window):
    """One decode step of sliding-window attention with a per-head sink
    over a packed arena (:func:`packed_paged_attention_decode`): the walk
    starts at the first page that holds one of the slot's last ``window``
    tokens, so a read costs ``window`` tokens whatever the length and the
    table columns before that page are never read (they may name blocks
    that were given up or never held); the online softmax opens at the
    sink (``m = sink, l = 1, acc = 0``: one more column, with no value).
    ``sinks (h,)`` float32, or None for a window without a sink."""
    return _walk_pages("swa_paged_attention_decode", _chunk_latent, q,
                       (arena,), block_table, lengths, scale, kvh=kvh,
                       v_streams=(0,), dv=dv, window=window, sinks=sinks)


def swa_mla_paged_attention_decode(q, arena, block_table, lengths, *,
                                   scale, rank, window):
    """One decode step of latent attention under a sliding ``window``
    (:func:`mla_paged_attention_decode` with the window walk of
    :func:`swa_paged_attention_decode`, no sink): the walk starts at the
    first page that holds one of the slot's last ``window`` tokens, so the
    table may cycle a slot's columns over a ring of blocks. Returns
    ``o_latent (b, h, rank)``."""
    return _walk_pages("swa_mla_paged_attention_decode", _chunk_latent, q,
                       (arena,), block_table, lengths, scale, kvh=1,
                       v_streams=(0,), dv=rank, window=window)


# ---------------------------------------------------------------------------
# learned sparse attention over a latent cache: the indexer's scores over a
# second arena of the same blocks, and the read of the selected rows
# ---------------------------------------------------------------------------

def _index_scores(q, w, keys, key_block=None):
    """``I = sum_j w_j ReLU(q_j . k)``: ``q (b, s, H, d)``, ``w (b, s,
    H)`` float32, ``keys (b, T, d)`` -> ``(b, s, T)`` float32 (fp32
    accumulation, operands as stored). ``key_block`` evaluates the keys in
    blocks of that many, so that the per-head intermediate ``(b, s, H,
    block)`` fits beside a long table."""
    w = w.astype(jnp.float32)

    def block(kb):
        dots = jnp.einsum("bshd,btd->bsht", q, kb,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bsht,bsh->bst", jnp.maximum(dots, 0.0), w)

    b, t, d = keys.shape
    if key_block is None or t <= key_block:
        return block(keys)
    pad = -t % key_block                # zero keys: their scores are cut
    keys = jnp.pad(keys, ((0, 0), (0, pad), (0, 0)))
    out = jax.lax.map(block, jnp.moveaxis(
        keys.reshape(b, (t + pad) // key_block, key_block, d), 1, 0))
    return jnp.moveaxis(out, 0, 2).reshape(b, q.shape[1], t + pad)[..., :t]


def dsa_index_scores_reference(q, w, key_arena, block_table, *,
                               key_block=None):
    """Gathered path of the indexer, any ``s``: the key arena ``(nb, bs,
    d)`` gathered through the table into timeline order, ``q (b, s, H,
    d)`` and ``w (b, s, H)`` against it. Returns ``(b, s, mb * bs)``
    float32, unmasked."""
    b = q.shape[0]
    keys = key_arena[block_table].reshape(b, -1, key_arena.shape[-1])
    return _index_scores(q, w, keys, key_block)


# Pages one copy may take when the table names them in a row: a key page is
# 4 KiB (16 tokens x 128 x 2 B) and a copy costs about 40 ns to issue
# whatever its size, so page-by-page the indexer's walk is bound by issuing
# copies at a ninth of the HBM roofline (my chip run, PR 37). A prefix
# prefilled into a fresh pool lies in consecutive blocks, rising or (the
# block manager pops its free list from the end) FALLING, and an aligned
# run of _RUN of them moves as one copy; anything else moves page by page.
# :func:`_run_directions` is the ONE place that says which groups those are:
# the kernel reads its verdict a group (a scalar-prefetch operand) and the
# caller turns a falling group's scores, which arrive in reverse, back.
_RUN = 8


def _run_directions(block_table, lengths, bs, run):
    """``(b, groups)`` int32: +1 where an aligned group of ``run`` table
    columns, wholly live, names rising consecutive blocks, -1 where falling
    ones (the kernel copies either as one piece, lowest block first), 0
    where its pages move one by one."""
    b, mb = block_table.shape
    groups = -(-mb // run)
    tbl = jnp.pad(block_table, ((0, 0), (0, groups * run - mb)),
                  constant_values=-1).reshape(b, groups, run)
    step = jnp.arange(run)
    rising = jnp.all(tbl == tbl[..., :1] + step, axis=-1)
    falling = jnp.all(tbl == tbl[..., :1] - step, axis=-1)
    n_pages = jnp.maximum(-(-jnp.minimum(lengths, mb * bs) // bs), 1)
    live = (jnp.arange(groups) + 1) * run <= n_pages[:, None]
    return jnp.where(live & (run > 1), rising.astype(jnp.int32)
                     - falling.astype(jnp.int32), 0)


def _index_kernel(tbl_ref, len_ref, dir_ref, q_ref, w_ref, hbm, o_ref, buf,
                  sems, parity, *, bs, mb, ppc, run):
    """The page walk of :func:`_walk_kernel` over ONE key arena that
    returns scores and no softmax: a grid step is a slot; its live pages
    are copied ``ppc`` to a chunk into one of two buffers (the next
    chunk's, or the next slot's first, in flight meanwhile); a chunk is
    the ``(rows, d)`` matrix it is in memory, ``q (H, d)`` against it in
    one product, ReLU, the heads' weighted sum, and the chunk's ``(1,
    rows)`` scores go to row c of the slot's output. Rows past the length
    hold what the buffer held: the caller masks by position. Live pages
    move ``run`` to a copy where :func:`_run_directions` says an aligned
    group of table columns names consecutive blocks (``dir_ref``; a falling
    group's pages, and so its scores, arrive in reverse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    n_slots = pl.num_programs(0)

    def pages_of(slot):
        return jnp.maximum(pl.cdiv(jnp.minimum(len_ref[slot], mb * bs), bs),
                           1)

    def each_live_page(slot, n_pages, c, b_, fn):
        """``fn`` on every copy of chunk c's live pages: the same copies,
        in the same order, whether they are started or waited for."""
        n_live = jnp.minimum(n_pages - c * ppc, ppc)

        def group(g, carry):
            p0 = g * run
            col = c * ppc + p0

            def page_by_page():
                def page_copy(p, carry):
                    fn(pltpu.make_async_copy(
                        hbm.at[tbl_ref[slot, col + p]], buf.at[b_, p0 + p],
                        sems.at[b_]))
                    return carry
                jax.lax.fori_loop(0, jnp.minimum(n_live - p0, run),
                                  page_copy, 0)

            if run == 1:
                page_by_page()
                return carry
            way = dir_ref[slot, col // run]

            @pl.when(way != 0)
            def _whole():
                lowest = tbl_ref[slot, col] - jnp.where(way < 0, run - 1, 0)
                fn(pltpu.make_async_copy(
                    hbm.at[pl.ds(lowest, run)], buf.at[b_, pl.ds(p0, run)],
                    sems.at[b_]))

            pl.when(way == 0)(page_by_page)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n_live, run), group, 0)

    def start(slot, n_pages, c, b_):
        each_live_page(slot, n_pages, c, b_, lambda cp: cp.start())

    n_pages = pages_of(i)
    n_chunks = pl.cdiv(n_pages, ppc)

    @pl.when(i == 0)
    def _first():
        parity[0] = 0
        start(i, n_pages, 0, 0)

    base = parity[0]
    q = q_ref[0]
    w = w_ref[0].astype(jnp.float32)                        # (H, 1)

    def fold(c, carry):
        b_ = (base + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _next_chunk():
            start(i, n_pages, c + 1, 1 - b_)

        @pl.when((c + 1 == n_chunks) & (i + 1 < n_slots))
        def _next_slot():
            nxt = jnp.minimum(i + 1, n_slots - 1)
            start(nxt, pages_of(nxt), 0, 1 - b_)

        each_live_page(i, n_pages, c, b_, lambda cp: cp.wait())
        k = buf[b_]
        k = k.reshape(-1, k.shape[-1])
        dots = jax.lax.dot_general(
            q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (H, rows)
        o_ref[0, pl.ds(c, 1), :] = jnp.sum(
            jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, n_chunks, fold, 0)
    parity[0] = (base + n_chunks) % 2


@functools.partial(jax.jit, static_argnames=("ppc", "run", "interpret"))
def _index_pallas_call(q, w, key_arena, block_table, lengths, *, ppc, run,
                       interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, d = q.shape
    _, bs, _ = key_arena.shape
    mb = block_table.shape[1]
    chunks, rows = -(-mb // ppc), ppc * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((1, h, 1), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, chunks, rows), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppc) + key_arena.shape[1:],
                                   key_arena.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    ways = _run_directions(block_table, lengths, bs, run) if run > 1 \
        else jnp.zeros((b, 1), jnp.int32)
    out = pl.pallas_call(
        functools.partial(_index_kernel, bs=bs, mb=mb, ppc=ppc, run=run),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, chunks, rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="dsa_index_scores_decode",
    )(block_table, lengths, ways, q, w.astype(jnp.float32)[:, :, None],
      key_arena)
    out = out.reshape(b, chunks * rows)[:, :-(-mb // run) * run * bs]
    if run > 1:         # a falling run's pages came lowest block first
        pages = out.reshape(b, -1, run, bs)
        out = jnp.where((ways < 0)[:, :, None, None], pages[:, :, ::-1],
                        pages).reshape(b, -1)
    return out[:, :mb * bs]


def dsa_index_scores_decode(q, w, key_arena, block_table, lengths):
    """One decode step of the indexer: ``q (b, H, d)``, ``w (b, H)``
    against every live key of the slot in the key arena ``(nb, bs, d)``,
    walked page by page through the table (never gathered). Returns ``(b,
    mb * bs)`` float32; entries at positions ``>= lengths`` are undefined
    (the caller masks them)."""
    mb = block_table.shape[1]
    ppc = _pages_per_chunk(mb, [(key_arena.shape[1:], key_arena.dtype)])
    run = _RUN if ppc % _RUN == 0 else 1     # groups must not straddle chunks
    return _index_pallas_call(q, w, key_arena, block_table, lengths,
                              ppc=ppc, run=run,
                              interpret=_fused._FORCE_INTERPRET)


def selected_rows(arena, block_table, ids):
    """Token ids ``(b, ..., k)`` on each slot's timeline -> their rows of
    the arena ``(nb, bs, w)`` through the table: ``(b, ..., k, w)``."""
    bs = arena.shape[1]
    shape = ids.shape
    flat = ids.reshape(shape[0], -1)
    blk = jnp.take_along_axis(block_table, flat // bs, axis=1)
    rows = arena.reshape(-1, arena.shape[-1])[blk * bs + flat % bs]
    return rows.reshape(shape + (arena.shape[-1],))


def dsa_sparse_mla_reference(q, arena, block_table, ids, n_valid, *, scale,
                             rank, q_block=None, valid_len=None):
    """Gathered path of the selected latent read, any ``s``: ``q (b, s, h,
    w)`` attends to the rows ``ids (b, s, k)`` of its slot's timeline and
    to those only, the first ``n_valid (b, s)`` of them (the rest are
    masked; an id past the valid ones must still name a row of the slot).
    fp32 softmax, the value the row's first ``rank`` columns; ``q_block``
    as in :func:`mla_paged_attention_reference`; with ``valid_len`` the
    query blocks wholly at or past it are skipped (zeros). Returns ``(b, s,
    h, rank)``."""
    k = ids.shape[-1]

    def read(qb, _, idb, nb_):
        rows = selected_rows(arena, block_table, idb)        # (b, n, k, w)
        scores = jnp.einsum("bshw,bskw->bhsk", qb, rows,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(k)[None, None, :] < nb_[:, :, None]
        scores = jnp.where(mask[:, None], scores, jnp.float32(_NEG))
        probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
        return jnp.einsum("bhsk,bskr->bshr", probs,
                          rows[..., :rank]).astype(q.dtype)

    return _in_query_blocks(read, q, n_valid, q_block, ids, n_valid,
                            valid_len=valid_len)


def _sparse_kernel(n_ref, q_ref, rows_ref, o_ref, *, scale, rank):
    """One slot: ``q (h, w)`` against its ``k`` gathered rows ``(k, w)``,
    the first ``n`` of them valid; softmax; the value the rows' first
    ``rank`` columns."""
    from jax.experimental import pallas as pl
    n = n_ref[pl.program_id(0)]
    q, rows = q_ref[0], rows_ref[0]
    s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    seen = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
    s = jnp.where(seen, s, _NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.dot(p.astype(rows.dtype), rows[:, :rank],
                  preferred_element_type=jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _sparse_pallas_call(q, rows, n_valid, *, scale, rank, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, w = q.shape
    k = rows.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i, n: (i, 0, 0)),
                  pl.BlockSpec((1, k, w), lambda i, n: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, h, rank), lambda i, n: (i, 0, 0)))
    return pl.pallas_call(
        functools.partial(_sparse_kernel, scale=scale, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_SPARSE_VMEM_LIMIT),
        interpret=interpret, name="dsa_sparse_mla_decode",
    )(n_valid, q, rows.astype(q.dtype))


# a slot's 2,048 gathered rows of 640 (2.6 MB) are pipelined whole, twice,
# beside the (heads, 2048) fp32 scores: past Mosaic's default of 16 MiB
_SPARSE_VMEM_LIMIT = 48 * 1024 * 1024


def dsa_sparse_mla_decode(q, arena, block_table, ids, n_valid, *, scale,
                          rank):
    """One decode step of the selected latent read: ``q (b, h, w)``
    attends to the rows ``ids (b, k)`` of its slot's timeline, the first
    ``n_valid (b,)`` of them. The rows are gathered through the table
    (``selected_rows``: k rows a slot, never the table) and the latent
    step over them is ONE named Pallas call. Returns ``o_latent (b, h,
    rank)``."""
    rows = selected_rows(arena, block_table, ids)            # (b, k, w)
    return _sparse_pallas_call(q, rows, n_valid.astype(jnp.int32),
                               scale=scale, rank=rank,
                               interpret=_fused._FORCE_INTERPRET)


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
    if _FORCE_INT8_REFERENCE:
        return paged_attention_int8_reference(
            q[:, None], k_codes, v_codes, k_scales, v_scales,
            block_table, lengths, scale=scale)[:, 0]
    if not _kernel_ok_int8(k_codes):
        return _int8_decode_fallback(
            q, k_codes, v_codes, k_scales, v_scales, block_table,
            lengths, scale=scale)
    return _walk_call("paged_attention_decode_int8", _chunk_dequantized,
                      q, (k_codes, v_codes, k_scales, v_scales),
                      block_table, lengths, scale)
