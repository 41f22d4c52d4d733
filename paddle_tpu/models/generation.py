"""Autoregressive generation: KV cache + jitted decode loop.

Reference parity: PaddleNLP GenerationMixin (greedy/sampling decode with
cache) and the reference inference engine's autoregressive path (SURVEY
§2.1 Inference, §3.5 AnalysisPredictor) — verify.

TPU-native design: the KV cache is a functional pytree of preallocated
(b, max_len, kv_heads, head_dim) arrays updated with
``lax.dynamic_update_slice`` (static shapes — no concat-growing cache,
which would retrace every step). ONE pure step function serves both
prefill (token block of length s, pos=0) and decode (length 1); it is
jitted once per sampling config and cached on the model, so repeated
``generate()`` calls reuse the compiled programs. Sampling
(temperature / top-k / top-p) runs inside the program.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import framework
from ..tensor import Tensor

__all__ = ["GenerationMixin", "sample_logits", "build_decode_step",
           "forward_accepts_pad"]


def sample_logits(logits, key, temperature=1.0, top_k=0, top_p=1.0):
    """Sample token ids from (b, V) logits (pure jax; runs inside the
    jitted decode step). temperature<=0 → greedy."""
    if temperature is None or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.asarray(temperature, logits.dtype)
    v = logits.shape[-1]
    want_k = bool(top_k) and 0 < top_k < v
    if want_k and top_p >= 1.0:
        # only the kth value is needed: lax.top_k (O(V·k) selection)
        # instead of a full O(V log V) sort
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    elif top_p < 1.0:
        # ONE descending sorted pass serves both filters: the top-k
        # threshold is sorted[k-1], and masking values < kth inside the
        # sorted array equals re-sorting the filtered logits (the kept
        # prefix is unchanged, the dropped tail becomes -inf)
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        if want_k:
            kth = sorted_desc[..., top_k - 1][..., None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
            sorted_desc = jnp.where(sorted_desc < kth, -jnp.inf,
                                    sorted_desc)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest set with cumulative prob >= top_p (always
        # keep the best token)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def cached_attention(qv, kv_, vv, ckv, cvv, posv, *, scale, cos=None,
                     sin=None, window=None, pad=None, block_table=None,
                     kv_scales=None):
    """KV-cache attention step (pure jax), shared by every causal LM:
    optional RoPE at offset ``posv`` (cos=None skips it — e.g. GPT's
    learned positions), k/v written into the preallocated cache with
    dynamic_update_slice, causal attention over cache[:pos+s]. GQA uses
    grouped einsums — the kv cache is never materialized at q-head
    count. Static shapes: one compiled program serves every position.

    ``pad`` (b,) int32: per-row LEFT-padding counts for ragged batches
    (reference decoding handles padded batches — SURVEY §3.5). Rows'
    RoPE positions are shifted back by their pad count and cache slots
    below ``pad`` are masked out of every later attention.

    ``posv`` may also be a (b,) vector — per-row write offsets for the
    continuous-batching slot pool (serving/): each row advances its own
    timeline, so one compiled step serves slots at arbitrary decode
    depths. Per-row writes vmap the dynamic_update_slice over the batch
    dim; the causal mask broadcasts per row.

    ``block_table`` (b, max_blocks) int32 switches to the PAGED layout:
    ``ckv``/``cvv`` are shared ``(num_blocks, block_size, kvh, d)``
    arenas, row r's timeline position t lives at arena block
    ``block_table[r, t // block_size]`` offset ``t % block_size``.
    Writes scatter into the arena (positions past the table width are
    routed to the reserved trash block 0); reads either run the Pallas
    paged-attention kernel (TPU, s=1) or gather the table into the
    dense timeline order and run the IDENTICAL einsum/mask/softmax
    sequence as the dense path — paged greedy decode is bit-identical
    to dense. Prompts are unpadded in paged mode (``pad`` ignored,
    positions start at 0). With ``kv_scales=(sk, sv)`` the arenas hold
    int8 codes and the scales arrays ``(num_blocks, block_size, kvh)``
    per-vector absmaxes (EQuARX recipe; returns 5-tuple
    ``(out, ck, cv, sk, sv)`` instead of 3)."""
    b, s, h, d = qv.shape
    posv = jnp.asarray(posv, jnp.int32)
    paged = block_table is not None
    if paged:
        if posv.ndim == 0:          # paged timelines are always per-row
            posv = jnp.broadcast_to(posv, (b,))
        pad = None
    per_row = posv.ndim == 1                  # (b,) slot-pool positions
    if per_row and pad is None:
        pad = jnp.zeros((b,), jnp.int32)
    if cos is not None:
        if pad is None:
            from ..ops.pallas.fused import fused_rope
            c = jax.lax.dynamic_slice_in_dim(cos, posv, s,
                                             0).astype(qv.dtype)
            sn = jax.lax.dynamic_slice_in_dim(sin, posv, s,
                                              0).astype(qv.dtype)
            qv, kv_ = fused_rope(qv, kv_, c, sn)
        else:
            # per-row positions: real-token index = slot - pad  (left
            # padding keeps real tokens contiguous at the end)
            p2 = posv[:, None] if per_row else posv
            positions = jnp.clip(
                p2 + jnp.arange(s)[None, :] - pad[:, None], 0, None)
            c = cos[positions].astype(qv.dtype)      # (b, s, d)
            sn = sin[positions].astype(qv.dtype)

            def rope(x):
                x1, x2 = jnp.split(x, 2, axis=-1)
                rot = jnp.concatenate([-x2, x1], axis=-1)
                return x * c[:, :, None, :] + rot * sn[:, :, None, :]
            qv, kv_ = rope(qv), rope(kv_)
    if paged:
        from ..ops.pallas import paged_attention as _pa
        bs_blk, mb = ckv.shape[1], block_table.shape[1]
        tpos = posv[:, None] + jnp.arange(s)[None, :]        # (b, s)
        blk_idx = tpos // bs_blk
        # chunked-prefill pad columns / dead slots can aim past the
        # table width — route those writes to the trash block 0, never
        # out of bounds or into another slot's blocks
        oob = blk_idx >= mb
        blk = jnp.where(
            oob, 0, jnp.take_along_axis(
                block_table, jnp.clip(blk_idx, 0, mb - 1), axis=1))
        off = jnp.where(oob, 0, tpos % bs_blk)
        if kv_scales is not None:                    # int8 KV arenas
            kq, ks = _pa.quantize_kv(kv_)
            vq, vs = _pa.quantize_kv(vv)
            ck = ckv.at[blk, off].set(kq.astype(ckv.dtype))
            cv = cvv.at[blk, off].set(vq.astype(cvv.dtype))
            sk = kv_scales[0].at[blk, off].set(ks)
            sv = kv_scales[1].at[blk, off].set(vs)
            if s == 1 and window is None:
                # bandwidth-true decode: dequant INSIDE the read
                # (Pallas int8 kernel on TPU, per-block scan fallback
                # off-TPU) — the dense fp32 KV transient of the old
                # dequant-then-gather path never materializes
                out = _pa.paged_attention_decode_int8(
                    qv[:, 0], ck, cv, sk, sv, block_table, posv + 1,
                    scale=scale)
                return out[:, None].astype(qv.dtype), ck, cv, sk, sv
            # s > 1 (chunked prefill / speculative verify window):
            # compute-bound, batch-1-ish — the gathered dequant stays
            k_read = _pa.dequantize_kv(_pa.paged_gather(ck, block_table),
                                       _pa.paged_gather(sk, block_table))
            v_read = _pa.dequantize_kv(_pa.paged_gather(cv, block_table),
                                       _pa.paged_gather(sv, block_table))
        else:
            ck = ckv.at[blk, off].set(kv_.astype(ckv.dtype))
            cv = cvv.at[blk, off].set(vv.astype(cvv.dtype))
            if s == 1 and window is None and _pa._kernel_ok(ck):
                out = _pa.paged_attention_decode(
                    qv[:, 0], ck, cv, block_table, posv + 1,
                    scale=scale)
                return out[:, None].astype(qv.dtype), ck, cv
            k_read = _pa.paged_gather(ck, block_table)
            v_read = _pa.paged_gather(cv, block_table)
    elif per_row:
        if s == 1:
            def upd(cachev, blockv):
                return jax.vmap(
                    lambda cr, xr, p: jax.lax.dynamic_update_slice(
                        cr, xr, (p, 0, 0)))(cachev,
                                            blockv.astype(cachev.dtype),
                                            posv)
            ck = upd(ckv, kv_)
            cv = upd(cvv, vv)
        else:
            # speculative verify: a k+1-wide per-row write. Scatter
            # (not dynamic_update_slice) because jax DROPS out-of-bounds
            # scatter updates — a draft window hanging past max_len
            # near capacity just loses its junk tail instead of
            # clamping backward over valid cache entries
            rows = jnp.arange(b)[:, None]
            tpos = posv[:, None] + jnp.arange(s)[None, :]
            ck = ckv.at[rows, tpos].set(kv_.astype(ckv.dtype))
            cv = cvv.at[rows, tpos].set(vv.astype(cvv.dtype))
        k_read, v_read = ck, cv
    else:
        ck = jax.lax.dynamic_update_slice(ckv, kv_.astype(ckv.dtype),
                                          (0, posv, 0, 0))
        cv = jax.lax.dynamic_update_slice(cvv, vv.astype(cvv.dtype),
                                          (0, posv, 0, 0))
        k_read, v_read = ck, cv
    kvh = k_read.shape[2]
    g = h // kvh
    qg = qv.reshape(b, s, kvh, g, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                        k_read.astype(jnp.float32)) * scale
    t_idx = jnp.arange(k_read.shape[1])
    if per_row:
        q_idx = posv[:, None] + jnp.arange(s)[None, :]     # (b, s)
        mask = t_idx[None, None, :] <= q_idx[:, :, None]   # (b, s, T)
        if window is not None:
            mask = mask & (t_idx[None, None, :]
                           > q_idx[:, :, None] - int(window))
    else:
        q_idx = posv + jnp.arange(s)
        mask = t_idx[None, :] <= q_idx[:, None]        # (s, T) causal
        if window is not None:                 # sliding window: last W
            mask = mask & (t_idx[None, :] > q_idx[:, None] - int(window))
        mask = mask[None]                              # (1|b, s, T)
    if pad is not None:                        # padded slots never attend
        mask = mask & (t_idx[None, None, :] >= pad[:, None, None])
    scores = jnp.where(mask[:, None, None], scores,
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(v_read.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_read)
    out = out.reshape(b, s, h, d).astype(qv.dtype)
    if paged and kv_scales is not None:
        return out, ck, cv, sk, sv
    return out, ck, cv


def latent_cached_attention(q_lat, latent, arena, posv, block_table, *,
                            scale, rank, window=None, select=None,
                            valid_len=None):
    """The latent-cache (MLA) step, beside :func:`cached_attention`: the
    cache keeps ONE row a token a layer, ``[c_kv (rank) | k_pe]``, shared
    by every head, and every read is the absorbed form.

    ``q_lat (b, s, h, rank + rope)``: per head ``[q_nope W_kvb,k^T |
    q_pe]`` (RoPE applied); ``latent (b, s, rank + rope)``: ``[c_kv
    after its norm | k_pe after RoPE]``; ``arena (num_blocks, block_size,
    w)`` with ``w`` the row padded to whole 128-lane tiles (the pad
    columns hold zeros); ``posv (b,)`` per-row write offsets;
    ``block_table (b, max_blocks)``. The write scatters the rows through
    the table (positions past its width land in the trash block 0, as in
    ``cached_attention``). The s = 1 read walks the slot's live pages in
    the Pallas kernel where the arena tiles (TPU); every other read (a
    prefill chunk, the CPU lane) gathers the table.

    ``window``: the read covers the last ``window`` positions only, and
    the table may cycle a slot's columns over a ring of blocks (the rules
    of :func:`packed_cached_attention`: the walk starts at the window's
    first page, the gathered read takes only the columns a window spans).
    ``select = (ids (b, s, k), n_valid (b, s))``: the read attends to
    those token ids of the slot's timeline and to no others, the first
    ``n_valid`` of each row's ``k``; it gathers ROWS (``block_table[id //
    bs] * bs + id % bs``), never the table; ``valid_len`` (a scalar: the
    columns of a right-padded chunk that hold a prompt token) lets the s >
    1 selected read skip the query blocks that are all padding. Returns
    ``(o_latent (b, s, h, rank), arena)``: the caller expands through
    ``W_kvb,v``."""
    from ..ops.pallas import paged_attention as _pa
    b, s, h, _ = q_lat.shape
    w = arena.shape[-1]
    posv = jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (b,))
    bs_blk, mb = arena.shape[1], block_table.shape[1]
    tpos = posv[:, None] + jnp.arange(s)[None, :]            # (b, s)
    blk_idx = tpos // bs_blk
    oob = blk_idx >= mb
    blk = jnp.where(oob, 0, jnp.take_along_axis(
        block_table, jnp.clip(blk_idx, 0, mb - 1), axis=1))
    off = jnp.where(oob, 0, tpos % bs_blk)

    def widen(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, w - x.shape[-1])])

    arena = arena.at[blk, off].set(widen(latent).astype(arena.dtype))
    q_lat = widen(q_lat)
    kernel = s == 1 and _pa._kernel_ok(arena)
    # a long chunk's scores, a query block at a time
    q_block = 128 if s > 128 and s % 128 == 0 else None
    if select is not None:
        ids, n_valid = select
        # an id past the valid ones names the slot's first row: masked,
        # but read, and so it must not be another slot's (or a NaN)
        ids = jnp.where(jnp.arange(ids.shape[-1]) < n_valid[..., None],
                        ids, 0)
        if kernel:
            out = _pa.dsa_sparse_mla_decode(
                q_lat[:, 0], arena, block_table, ids[:, 0], n_valid[:, 0],
                scale=scale, rank=rank)[:, None]
        else:
            out = _pa.dsa_sparse_mla_reference(
                q_lat, arena, block_table, ids, n_valid, scale=scale,
                rank=rank, q_block=q_block, valid_len=valid_len)
    elif window is not None:
        if kernel:
            out = _pa.swa_mla_paged_attention_decode(
                q_lat[:, 0], arena, block_table, posv + 1, scale=scale,
                rank=rank, window=window)[:, None]
        else:
            out = _pa.mla_paged_attention_reference(
                q_lat, arena, block_table, posv + s, scale=scale, rank=rank,
                window=window, q_block=q_block)
    elif kernel:
        out = _pa.mla_paged_attention_decode(
            q_lat[:, 0], arena, block_table, posv + 1, scale=scale,
            rank=rank)[:, None]
    else:
        out = _pa.mla_paged_attention_reference(
            q_lat, arena, block_table, posv + s, scale=scale, rank=rank)
    return out, arena


def latent_index_scores(q_idx, w_idx, k_idx, key_arena, posv, block_table):
    """The indexer of learned sparse attention over a paged cache: the new
    tokens' index keys ``k_idx (b, s, d)`` are written into ``key_arena
    (num_blocks, block_size, d)`` through the table (the latent rows'
    block ids: a token's key lies under the block that holds its row), and
    every query ``q_idx (b, s, H, d)`` with its head weights ``w_idx (b,
    s, H)`` scores every cached key of its slot: ``I = sum_j w_j ReLU(q_j
    . k)``, fp32 accumulation. The s = 1 read walks the live pages in the
    Pallas kernel (``dsa_index_scores_decode``) where the arena tiles;
    every other read gathers the table, a block of keys at a time.
    Returns ``(I (b, s, max_blocks * block_size) float32, key_arena)``
    with the positions past each row's own at ``-inf``."""
    from ..ops.pallas import paged_attention as _pa
    b, s = q_idx.shape[:2]
    posv = jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (b,))
    bs_blk, mb = key_arena.shape[1], block_table.shape[1]
    tpos = posv[:, None] + jnp.arange(s)[None, :]            # (b, s)
    blk_idx = tpos // bs_blk
    oob = blk_idx >= mb
    blk = jnp.where(oob, 0, jnp.take_along_axis(
        block_table, jnp.clip(blk_idx, 0, mb - 1), axis=1))
    off = jnp.where(oob, 0, tpos % bs_blk)
    key_arena = key_arena.at[blk, off].set(k_idx.astype(key_arena.dtype))
    q_idx = q_idx.astype(key_arena.dtype)
    if s == 1 and _pa._kernel_ok(key_arena):
        scores = _pa.dsa_index_scores_decode(
            q_idx[:, 0], w_idx[:, 0], key_arena, block_table,
            posv + 1)[:, None]
    else:
        scores = _pa.dsa_index_scores_reference(
            q_idx, w_idx, key_arena, block_table, key_block=1024)
    seen = jnp.arange(mb * bs_blk)[None, None, :] <= tpos[:, :, None]
    return jnp.where(seen, scores, -jnp.inf), key_arena


def packed_cached_attention(q, k, v, arena, posv, block_table, *, scale,
                            window=None, sinks=None):
    """The step of a cache with two kinds of layer, beside
    :func:`cached_attention` and :func:`latent_cached_attention`: grouped
    queries over a PACKED arena, full or under a sliding ``window`` with a
    per-head sink, K and V of different widths.

    ``q (b, s, h, dk)`` and ``k (b, s, kvh, dk)`` come with their RoPE
    applied, ``v (b, s, kvh, dv)`` with its scale; ``arena (num_blocks,
    block_size * kvh, w)`` keeps a page as the matrix the decode walk
    reads, row ``t * kvh + j`` token t of kv head j, ``[v | k | 0]`` with
    ``w`` whole 128-lane tiles; ``posv (b,)`` per-row write offsets;
    ``block_table (b, max_blocks)`` THIS layer's group's table. The write
    scatters through the table (positions past its width land in the
    trash block 0). Under a ``window`` the table may cycle a slot's
    columns over a ring of blocks: a write then lands on what the slot
    wrote a ring earlier, which no later read sees, and a read touches
    only the columns its window spans. The s = 1 read walks the pages in
    the Pallas kernel where the arena tiles (``paged_attention_decode``,
    or ``swa_paged_attention_decode`` under a window); every other read
    gathers. Returns ``(out (b, s, h, dv), arena)``."""
    from ..ops.pallas import paged_attention as _pa
    b, s, h, dk = q.shape
    kvh, dv = v.shape[2], v.shape[3]
    w = arena.shape[-1]
    posv = jnp.broadcast_to(jnp.asarray(posv, jnp.int32), (b,))
    bs_blk, mb = arena.shape[1] // kvh, block_table.shape[1]
    tpos = posv[:, None] + jnp.arange(s)[None, :]            # (b, s)
    blk_idx = tpos // bs_blk
    oob = blk_idx >= mb
    blk = jnp.where(oob, 0, jnp.take_along_axis(
        block_table, jnp.clip(blk_idx, 0, mb - 1), axis=1))
    off = jnp.where(oob, 0, tpos % bs_blk)
    rows = jnp.concatenate(
        [v, k, jnp.zeros((b, s, kvh, w - dv - dk), v.dtype)], axis=-1)
    arena = arena.at[blk[:, :, None],
                     off[:, :, None] * kvh + jnp.arange(kvh)].set(
        rows.astype(arena.dtype))
    q = jnp.pad(q, [(0, 0)] * 3 + [(dv, w - dv - dk)])
    if s == 1 and _pa._kernel_ok(arena):
        if window is None:
            out = _pa.packed_paged_attention_decode(
                q[:, 0], arena, block_table, posv + 1, scale=scale, kvh=kvh,
                dv=dv)
        else:
            out = _pa.swa_paged_attention_decode(
                q[:, 0], arena, block_table, posv + 1, sinks, scale=scale,
                kvh=kvh, dv=dv, window=window)
        return out[:, None], arena
    out = _pa.packed_paged_attention_reference(
        q, arena, block_table, posv + s, scale=scale, kvh=kvh, dv=dv,
        window=window, sinks=sinks,
        q_block=128 if s > 128 and s % 128 == 0 else None)
    return out, arena


def forward_accepts_pad(cls) -> bool:
    """Whether ``cls.forward`` takes per-row ``pad`` counts (ragged /
    slot-pool decode). The inspect.signature probe is cached per class —
    it previously ran on every ragged generate() call."""
    cached = cls.__dict__.get("_fwd_accepts_pad")
    if cached is None:
        import inspect
        cached = "pad" in inspect.signature(cls.forward).parameters
        cls._fwd_accepts_pad = cached   # per-class; subclasses re-probe
    return cached


def forward_accepts_block_table(cls) -> bool:
    """Whether ``cls.forward`` threads a paged-KV ``block_table``
    through to ``cached_attention`` (the serving engine's paged mode
    needs it). Cached per class like :func:`forward_accepts_pad`."""
    cached = cls.__dict__.get("_fwd_accepts_block_table")
    if cached is None:
        import inspect
        cached = "block_table" in inspect.signature(
            cls.forward).parameters
        cls._fwd_accepts_block_table = cached
    return cached


def forward_accepts_valid_len(cls) -> bool:
    """Whether ``cls.forward`` takes ``valid_len``: how many of a
    right-padded chunk's columns hold a prompt token (a model whose
    per-row work is dear, a sort and a gather a row, skips the pad
    columns' blocks). Cached per class like :func:`forward_accepts_pad`."""
    cached = cls.__dict__.get("_fwd_accepts_valid_len")
    if cached is None:
        import inspect
        cached = "valid_len" in inspect.signature(cls.forward).parameters
        cls._fwd_accepts_valid_len = cached
    return cached


def build_decode_step(model, sample_kwargs, tree_holder,
                      all_positions=False):
    """The shared pure step: (params, bufs, token_block, cache_flat,
    pos, key) → (next_token, new_cache_flat). Serves prefill (block of
    length s at pos=0) and decode (length 1) — jit/retrace handles the
    two shapes within one compiled-function cache. Used by
    GenerationMixin.generate, beam search (sample_kwargs=None → returns
    next-token LOG-PROBS instead of a sampled token; the ``key`` arg is
    accepted and ignored) and inference.export_decoder.

    ``all_positions=True`` (requires sample_kwargs=None) returns the
    log-probs at EVERY position of the block, shape (b, s, V) — the
    speculative-verify head: one dispatch scores a whole candidate
    window (serving/spec.py)."""
    if all_positions and sample_kwargs is not None:
        raise ValueError("all_positions=True returns raw log-probs; "
                         "pass sample_kwargs=None")
    ptensors = [p for _, p in model.named_parameters()]
    btensors = [b for _, b in model.named_buffers()]
    takes_valid_len = forward_accepts_valid_len(type(model))

    def pure(pv, bv, token, cache_flat, pos, key=None, pad=None,
             block_table=None, last_index=None):
        saved = [(t, t._value) for t in ptensors + btensors]
        was_training = model.training
        try:
            for t, v in zip(ptensors, pv):
                t._value = v
            for t, v in zip(btensors, bv):
                t._value = v
            model.eval()   # no dropout inside the decode program
            cache = jax.tree.unflatten(tree_holder["tree"], [
                Tensor(c) for c in cache_flat])
            kw = {} if pad is None else {"pad": Tensor(pad)}
            if block_table is not None:     # paged-KV serving mode
                kw["block_table"] = Tensor(block_table)
            if last_index is not None and takes_valid_len:
                # chunked prefill: the columns past the last real token
                # are right-padding
                kw["valid_len"] = Tensor(last_index + 1)
            with framework.functional_mode(), framework.no_grad_guard():
                logits, new_cache = model.forward(
                    Tensor(token), cache=cache, pos=Tensor(pos), **kw)
            if all_positions:
                lv = logits._value              # (b, s, V) verify head
            elif last_index is None:
                lv = logits._value[:, -1, :]
            else:
                # chunked prefill: the last REAL token of a right-
                # padded chunk sits at a traced index, not at -1
                lv = jax.lax.dynamic_slice_in_dim(
                    logits._value, last_index, 1, axis=1)[:, 0, :]
            lv = lv.astype(jnp.float32)
            new_flat = [c._value for c in jax.tree.leaves(
                new_cache, is_leaf=lambda x: isinstance(x, Tensor))]
            if sample_kwargs is None:      # beam head: full log-probs
                return jax.nn.log_softmax(lv, axis=-1), tuple(new_flat)
            nt = sample_logits(lv, key, **sample_kwargs)
            return nt.astype(jnp.int32), tuple(new_flat)
        finally:
            for t, v in saved:
                t._value = v
            if was_training:
                model.train()

    return pure


def build_logits_step(model, tree_holder):
    """Beam-search head: build_decode_step with sample_kwargs=None."""
    return build_decode_step(model, None, tree_holder)


class GenerationMixin:
    """Adds ``generate()`` to a causal LM whose forward supports
    ``forward(input_ids, cache=cache, pos=pos) -> (logits, new_cache)``
    and which implements ``init_kv_cache(batch, max_len, dtype)``."""

    def _decode_fn(self, sample_kwargs):
        """Jitted decode step, cached on the model per sampling config
        (jax.jit caches by function identity — a fresh closure per call
        would recompile every generate())."""
        cache = self.__dict__.setdefault("_decode_fn_cache", {})
        key = tuple(sorted(sample_kwargs.items()))
        if key not in cache:
            tree_holder = {"tree": None}
            pure = build_decode_step(self, sample_kwargs, tree_holder)
            cache[key] = (jax.jit(pure, donate_argnums=(3,)), tree_holder)
        return cache[key]

    def _logits_fn(self):
        cache = self.__dict__.setdefault("_decode_fn_cache", {})
        if "__logits__" not in cache:
            tree_holder = {"tree": None}
            pure = build_logits_step(self, tree_holder)
            cache["__logits__"] = (jax.jit(pure, donate_argnums=(3,)),
                                   tree_holder)
        return cache["__logits__"]

    def _scan_decode_fn(self, sample_kwargs, n_steps):
        """The whole decode tail as ONE compiled program: a lax.scan of
        the shared step over ``n_steps`` tokens. Removes the per-token
        host dispatch round-trip of the Python loop (the reference's
        fused decoding / while-op analogue: fused_multi_transformer
        serving loop — verify). Sampling-key evolution matches the
        Python loop exactly (same split sequence)."""
        cache = self.__dict__.setdefault("_decode_fn_cache", {})
        key = ("__scan__", tuple(sorted(sample_kwargs.items())), n_steps)
        if key not in cache:
            tree_holder = {"tree": None}
            pure = build_decode_step(self, sample_kwargs, tree_holder)

            def scan_pure(pv, bv, tok0, cache_flat, start_pos, rkey,
                          pad=None):
                def body(carry, i):
                    tok, cf, k = carry
                    k, sub = jax.random.split(k)
                    nt, ncf = pure(pv, bv, tok[:, None], cf,
                                   start_pos + i, sub, pad)
                    return (nt, ncf, k), nt
                (_, cf, _), toks = jax.lax.scan(
                    body, (tok0, cache_flat, rkey),
                    jnp.arange(n_steps, dtype=jnp.int32))
                return toks, cf
            cache[key] = (jax.jit(scan_pure, donate_argnums=(3,)),
                          tree_holder)
        return cache[key]

    def _beam_search(self, ids, max_new, total, num_beams,
                     eos_token_id, length_penalty, pad=None):
        """Beam search over the cached decode step (reference: PaddleNLP
        BeamSearchScorer path — verify). Beams ride the batch dim: the
        cache is built at b·K rows and REORDERED (gather on dim 0)
        after each step's beam selection. ``pad`` (b,): per-row left-pad
        counts (ragged prompts) — replicated K× alongside the cache."""
        b, s = ids.shape
        K = num_beams
        ids_arr = ids._value.astype(jnp.int32)
        step_fn, tree_holder = self._logits_fn()
        # prefill ONCE at batch b, then replicate the cache K× — beams
        # are identical at t=0, so prefilling b·K rows would waste
        # (K-1)/K of the prompt FLOPs
        cache = self.init_kv_cache(b, total)
        flat, tree = jax.tree.flatten(
            cache, is_leaf=lambda x: isinstance(x, Tensor))
        tree_holder["tree"] = tree
        cache_flat = tuple(c._value for c in flat)
        ptensors = [p for _, p in self.named_parameters()]
        btensors = [t for _, t in self.named_buffers()]
        pv = [p._value for p in ptensors]
        bv = [t._value for t in btensors]

        lp, cache_flat = step_fn(pv, bv, ids_arr,
                                 cache_flat, jnp.asarray(0, jnp.int32),
                                 None, pad)
        cache_flat = tuple(jnp.repeat(c, K, axis=0) for c in cache_flat)
        pad_rep = None if pad is None else jnp.repeat(pad, K, axis=0)
        V = lp.shape[-1]
        scores, first = jax.lax.top_k(lp, K)    # (b, K)
        beam_scores = scores                    # (b, K)
        sequences = first.reshape(b, K, 1)      # (b, K, new_len)
        finished = jnp.zeros((b, K), bool)
        if eos_token_id is not None:
            finished = first == eos_token_id
        beam_lens = jnp.ones((b, K), jnp.float32)   # per-beam gen length
        tok = first.reshape(b * K)

        NEG = jnp.float32(-1e9)
        if eos_token_id is not None:       # loop-invariant: hoisted
            eos_only = jnp.full((V,), NEG).at[eos_token_id].set(0.0)
        for i in range(1, max_new):
            pos = jnp.asarray(s + i - 1, jnp.int32)
            lp, cache_flat = step_fn(pv, bv, tok[:, None].astype(
                jnp.int32), cache_flat, pos, None, pad_rep)
            lp = lp.reshape(b, K, V)
            if eos_token_id is not None:
                # finished beams: only eos continues, at zero cost
                lp = jnp.where(finished[..., None], eos_only[None, None],
                               lp)
            cand = beam_scores[..., None] + lp          # (b, K, V)
            flat_cand = cand.reshape(b, K * V)
            beam_scores, idx = jax.lax.top_k(flat_cand, K)
            src_beam = idx // V                         # (b, K)
            new_tok = idx % V
            # reorder histories + cache rows by winning source beam
            gather = (jnp.arange(b)[:, None] * K + src_beam).reshape(-1)
            sequences = jnp.take_along_axis(
                sequences, src_beam[..., None], axis=1)
            sequences = jnp.concatenate(
                [sequences, new_tok[..., None]], axis=2)
            cache_flat = tuple(c[gather] for c in cache_flat)
            finished = jnp.take_along_axis(finished, src_beam, axis=1)
            beam_lens = jnp.take_along_axis(beam_lens, src_beam, axis=1)
            # unfinished beams grow; finished ones keep their length
            beam_lens = jnp.where(finished, beam_lens,
                                  jnp.float32(i + 1))
            if eos_token_id is not None:
                finished = finished | (new_tok == eos_token_id)
            tok = new_tok.reshape(b * K)
            if eos_token_id is not None and bool(finished.all()):
                break
        norm = jnp.power(beam_lens, length_penalty) \
            if length_penalty else 1.0
        best = jnp.argmax(beam_scores / norm, axis=1)   # (b,)
        best_seq = jnp.take_along_axis(
            sequences, best[:, None, None], axis=1)[:, 0]
        return Tensor(jnp.concatenate([ids_arr, best_seq], axis=1))

    def generate(self, input_ids, max_new_tokens: int = 20,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, do_sample: bool = False,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 max_length: Optional[int] = None, num_beams: int = 1,
                 length_penalty: float = 0.0, attention_mask=None,
                 use_scan_decode: Optional[bool] = None,
                 eos_check_every: int = 8):
        """Greedy (temperature<=0 / do_sample=False), sampled, or
        beam-search (num_beams>1) decoding with a preallocated KV cache
        and one jitted decode step.

        ``attention_mask`` (b, s) 0/1: LEFT-padded ragged prompts
        (zeros first, HF convention) — per-row RoPE offsets and key
        masking make batched ragged decode match per-sequence decode
        exactly (reference: PaddleNLP padded-batch decoding — verify).

        ``eos_check_every``: the eager loop's all-rows-finished exit
        needs a device→host sync (``bool(finished.all())``); checking
        only every N steps keeps dispatch pipelined. The output is
        identical either way — the return is ALWAYS (b, s+new) with
        finished rows eos-padded (an early exit pads the remaining
        columns in one shot instead of decoding them) — at most N-1
        extra masked decode steps run after the last row finishes.

        Returns (b, s+new) int Tensor of prompt + generated ids (rows
        that hit ``eos_token_id`` are padded with eos)."""
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(np.asarray(input_ids), jnp.int32))
        b, s = ids.shape
        pad = None
        if attention_mask is not None:
            if not forward_accepts_pad(type(self)):
                raise ValueError(
                    f"{type(self).__name__}.forward does not accept "
                    "per-row pad counts — ragged (attention_mask) "
                    "decoding is unsupported for this model; decode "
                    "unpadded batches instead")
            am = attention_mask.numpy() if isinstance(
                attention_mask, Tensor) else np.asarray(attention_mask)
            if am.shape != (b, s):
                raise ValueError(f"attention_mask shape {am.shape} != "
                                 f"prompt shape {(b, s)}")
            if not (np.sort(am, axis=1) == am).all():
                raise ValueError(
                    "attention_mask must be LEFT-padded (all zeros "
                    "before ones in every row)")
            pad = jnp.asarray(s - am.sum(axis=1), jnp.int32)   # (b,)
            if not bool((pad < s).all()):
                raise ValueError("attention_mask has an all-pad row")
        total = max_length or (s + max_new_tokens)
        max_new = total - s
        if max_new <= 0:
            return ids
        if do_sample and (temperature is None or temperature <= 0.0):
            temperature = 1.0   # PaddleNLP parity: do_sample defaults hot
        limit = getattr(getattr(self, "config", None),
                        "max_position_embeddings", None)
        if limit is not None and total > limit:
            from ..utils.enforce import OutOfRangeError
            raise OutOfRangeError(
                f"prompt ({s}) + new tokens ({max_new}) = {total} exceeds "
                f"max_position_embeddings={limit}",
                "positions past the RoPE/position table would silently "
                "clamp; raise max_position_embeddings or shorten the "
                "request")
        if use_scan_decode and eos_token_id is not None:
            raise ValueError("use_scan_decode=True cannot early-exit on "
                             "eos_token_id; drop one of the two")
        if num_beams > 1:
            if do_sample:
                raise ValueError("num_beams>1 with do_sample=True is not "
                                 "supported (beam sampling); use one or "
                                 "the other")
            if use_scan_decode:
                raise ValueError("use_scan_decode=True with num_beams>1 "
                                 "is not supported (beam reordering is "
                                 "a per-token host decision)")
            return self._beam_search(ids, max_new, total, num_beams,
                                     eos_token_id, length_penalty,
                                     pad=pad)
        if not do_sample:
            temperature = 0.0
        sample_kwargs = dict(temperature=temperature, top_k=top_k,
                             top_p=top_p)
        cache = self.init_kv_cache(b, total)
        flat, tree = jax.tree.flatten(
            cache, is_leaf=lambda x: isinstance(x, Tensor))
        decode, tree_holder = self._decode_fn(sample_kwargs)
        tree_holder["tree"] = tree
        cache_flat = tuple(c._value for c in flat)
        ptensors = [p for _, p in self.named_parameters()]
        btensors = [t for _, t in self.named_buffers()]
        pv = [p._value for p in ptensors]
        bv = [t._value for t in btensors]

        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        ids_arr = ids._value.astype(jnp.int32)
        # prefill: the same compiled step with a length-s block at pos 0
        tok, cache_flat = decode(pv, bv, ids_arr, cache_flat,
                                 jnp.asarray(0, jnp.int32), sub, pad)

        if use_scan_decode is None:
            # in-graph scan: one compiled program for the whole tail.
            # With an eos id the Python loop's early exit usually wins
            # (scan cannot break), so auto only without eos.
            use_scan_decode = eos_token_id is None
        if use_scan_decode and max_new > 1:
            scan_step, th2 = self._scan_decode_fn(sample_kwargs,
                                                  max_new - 1)
            th2["tree"] = tree
            toks, cache_flat = scan_step(pv, bv, tok, cache_flat,
                                         jnp.asarray(s, jnp.int32),
                                         key, pad)
            gen = jnp.concatenate([tok[:, None],
                                   jnp.moveaxis(toks, 0, 1)], axis=1)
            return Tensor(jnp.concatenate([ids_arr, gen], axis=1))

        out_tokens = [tok]
        finished = jnp.zeros((b,), bool)
        if eos_token_id is not None:
            finished = finished | (tok == eos_token_id)
        for i in range(1, max_new):
            key, sub = jax.random.split(key)
            pos = jnp.asarray(s + i - 1, jnp.int32)
            tok, cache_flat = decode(pv, bv, tok[:, None], cache_flat,
                                     pos, sub, pad)
            if eos_token_id is not None:
                tok = jnp.where(finished, eos_token_id, tok)
                finished = finished | (tok == eos_token_id)
            out_tokens.append(tok)
            # bool(finished.all()) forces a device→host round-trip that
            # stalls the dispatch pipeline — poll it only every
            # eos_check_every steps (output semantics are unchanged:
            # finished rows already pad with eos)
            if eos_token_id is not None and \
                    i % max(1, eos_check_every) == 0 and \
                    bool(finished.all()):
                break
        gen = jnp.stack(out_tokens, axis=1)
        if len(out_tokens) < max_new:
            # early eos exit: the contract is a STATIC (b, s+new) shape
            # with finished rows eos-padded — emit the skipped columns
            # directly instead of decoding them
            gen = jnp.concatenate(
                [gen, jnp.full((b, max_new - len(out_tokens)),
                               eos_token_id, gen.dtype)], axis=1)
        return Tensor(jnp.concatenate([ids_arr, gen], axis=1))
