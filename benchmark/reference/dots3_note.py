"""The plain reference for dots3-note-class configurations.

A straightforward float32 ``jax.numpy`` forward of the published block
(``model_type: dots3_note``): no kernel, no cache, no batching,
``default_matmul_precision("highest")``. One sequence at a time:

  embedding; per layer  x += Attn_l(RMSNorm(x));  x += FFN_l(RMSNorm(x));
  final RMSNorm; untied head.   RMSNorm eps ``rms_norm_eps``; no biases.

  ``layer_types[l]`` says full or sliding. Sizes (H, r_q, r_kv, d_n, d_r,
  d_v, theta): full layers the unprefixed keys, sliding layers ``swa_*``.
  Attn: y the normed input. c_q = a_q RMSNorm(y W_qa); [q_n | q_r] = c_q
        W_qb -> (H, d_n + d_r); [c_kv | k_r] = y W_kva; c_kv <- a_kv
        RMSNorm(c_kv); RoPE(theta), half-split rotate_half, on q_r and on
        the ONE k_r all heads share; [k_n | v] = c_kv W_kvb -> (H, d_n +
        d_v); score(t, s, h) = (q_n . k_n + q_r . k_r) / sqrt(d_n + d_r);
        softmax over the visible set V_t; o_h = g_h sum_s P v with g =
        sigmoid(y W_g) (H,); out = concat(o) W_o.
        `assumed`: a_q = sqrt(hidden / r_q), a_kv = sqrt(hidden / r_kv)
        (``apply_mla_qkv_lora_rescale``); the gate reads y.
  V_t, sliding layer: {s : t - sliding_window_size < s <= t}.
  V_t, full layer: q^I = c_q W^I_q -> (index_n_heads, index_head_dim);
        k^I = LayerNorm(y W^I_k) (weight and bias); RoPE(theta) on the
        FIRST d_r dims of each, the rest pass; w = (y W^I_w) /
        sqrt(index_n_heads * index_head_dim); I(t, s) = sum_j w_j
        ReLU(q^I_j(t) . k^I(s)); V_t = the index_topk s <= t of largest I,
        all of them while t + 1 <= index_topk (``lax.top_k``: exact).
  FFN, layers below ``first_k_dense_replace``: SwiGLU(intermediate_size).
  FFN elsewhere: ``reference/deepseek_v3.py``'s ``experts`` (sigmoid
        scores, top-k of s + e_score_correction_bias, no groups, weights
        s[idx] normalised, x routed_scaling_factor) + ONE shared SwiGLU.

Departures from a literal transcription, none of them in the mathematics:
attention is evaluated a GROUP OF HEADS at a time (``head_group``; k_n and
v of 36k tokens for 128 heads at once are 9 GB in float32) and, inside a
group, in blocks of query rows; a full layer's block scores every key and
masks to V_t; a sliding layer's block is given only the keys its rows'
windows can reach (the slice ``[lo - window + 1, lo + n)``: every key left
out is outside every window of the block); the indexer's scores are taken
a block of query rows at a time; the experts are evaluated one at a time
(``reference/deepseek_v3.py``), a layer's feed-forward half ``FFN_ROWS``
rows at a time and the head ``HEAD_COLS`` columns at a time; ``logits_at``
limits the head to the positions asked for. No vision or audio tower and no multi-token head: the
language model's config has no key for them.

``experts_held=(first, count)`` gives the reference a chip's share.
``forced_picks`` holds it to the system's routing (top-k of the router is
discontinuous); the indexer's selection is NOT forced: ``compare`` reports
the share of the reference's selected set the system selected. ``mutate``
breaks the reference on purpose, one published term at a time, and
``matmul_dtype`` rounds every product's operands through a coarser dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v3 import (_Ops, _matmul, _norm, _program,
                                             _static, _swiglu, experts,
                                             layer_weights, rms_norm)
from benchmark.reference.mimo_v2 import ring_tables

# The comparison's limits, each set between two readings on the chip at the
# published widths (PERF.md section 6, PR 37; ``tools/sparse_limits_probe.py``
# takes them; 8 seeds of the bf16 model, 2 of every broken variant).
#
# Relative rms error of the logits, the reference held to the system's
# expert picks, the indexer selecting for itself on both sides. The bf16
# model reads 0.00826-0.00829 without a cache (4,096 tokens, 513 rows) and
# 0.00843-0.00852 through both groups' caches (8,192 tokens, 40 rows; the
# cell's 18 runs print 0.0084-0.0086); the seeds differ by a hundredth of
# that (bf16 rounds at 2**-9 a product,
# about fifteen rounded products a layer on the residual path, 5 layers,
# and a 152k-wide head). A broken variant's reading carries the model's own
# error beside the term's (0.0108**2 = 0.0083**2 + 0.0069**2). Nearest: the
# indexer's ReLU dropped 0.01078-0.01080 (5 seeds), layer 0's selection used
# again in layer 1 0.0117, no indexer 0.0121 (random weights attend
# diffusely: 2,048 of 4,096 tokens more or fewer move a layer's output
# little; at 33k it reads 0.028, ``TIMED_LOGITS_TOLERANCE``), index_topk
# halved 0.0195, the sliding layers at the full layers' RoPE base 0.0206,
# the window halved 0.0357, no rescales 0.049, no gate 0.085-0.089, the
# reference in 8-bit floats 0.185, no shared expert 0.85. The limit lies
# between 0.00852 and 0.0108: thirty of the seeds' standard deviations
# above the largest bf16 reading, an eighth under the nearest variant (which
# fails the selected share below as well).
LOGITS_TOLERANCE = 0.0095
# share of (token, layer, k) expert picks on which system and reference
# agree when each routes for itself: top-8 of 256 biased sigmoid scores.
# The bf16 model reads 0.9927-0.9936; the reference in 8-bit floats, routing
# for itself, 0.9316-0.9324 (3 seeds, this model); ``reference/mimo_v2.py``
# read the same router without its selection bias at 0.52-0.54.
PICKS_TOLERANCE = 0.95
# share of the reference's selected set (full layers, the rows compared)
# that the system selected too. bf16 index scores move the set only at its
# margin: 0.99897-0.99899 without a cache, 0.99717-0.99754 through the
# caches (rows at 8k, where the margin is denser). The reference in 8-bit
# floats reads 0.9831-0.9832, without the ReLU 0.9280-0.9281 (5 seeds each),
# layer 0's selection used again in layer 1 0.897.
SELECTED_FLOOR = 0.99
# The two limits of ``timed_context``: the cache path at the context the
# cell is TIMED at (a 32,768-token document + a question + the answer, 16
# times index_topk) against the reference, at the rows past the document.
# There the selected read drops fifteen sixteenths of the context (half at
# check (a)'s 4,096), so the indexer's terms weigh more, and the selected
# set's margin is denser. Readings on the chip (PERF.md section 6, PR 37;
# ``tools/sparse_limits_probe.py --paths timed`` and the cell's own runs):
# the bf16 model's logits 0.0090, selected share 0.9940; the reference
# without the ReLU 0.0218 (share 0.577), without the indexer 0.0280, in 8-bit
# floats 0.186 (0.901). Each limit lies between bf16's reading and the
# nearest variant's, at about their geometric mean.
TIMED_LOGITS_TOLERANCE = 0.014
TIMED_SELECTED_FLOOR = 0.97
SEQ = 4096
# decode steps at the end of the timed context's check (one at a time)
TIMED_DECODE = 64
# rows of a layer's feed-forward half and columns of the head evaluated at
# once (at 34k tokens every (rows, hidden) float32 array is 0.64 GiB, the
# dense layer's two activations 5.5 GB, and sixteen experts one after another
# over all rows 5 GiB of them): with these, and attention a few heads at a
# time, a check's temporaries stay small beside what the serving path holds
# (PERF.md section 5 has each phase's peak)
FFN_ROWS, HEAD_COLS = 4096, 16384
# one published term a name: no indexer (every token visible on full
# layers); index_topk halved; the indexer's ReLU dropped; layer 0's
# selection used again in layer 1; the gate dropped; the window halved
# (257); the sliding layers at the full layers' rope_theta; the shared
# expert dropped; the two rescales at 1.0
MUTATIONS = ("indexer", "topk", "relu", "reuse_selection", "gate", "window",
             "rope_base", "shared", "rescale")
FULL, WINDOW = 0, 1
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


def layer_kinds(c: dict):
    return [_KINDS[t] for t in c["layer_types"][:c["num_hidden_layers"]]]


def sizes(c: dict, kind: int, mutate=()) -> dict:
    p = "swa_" if kind == WINDOW else ""
    theta = c["rope_theta"] if kind == FULL or "rope_base" in mutate \
        else c["swa_rope_theta"]
    out = {k: c[p + k] for k in ("num_attention_heads", "q_lora_rank",
                                 "kv_lora_rank", "qk_nope_head_dim",
                                 "qk_rope_head_dim", "v_head_dim")}
    out["theta"] = float(theta)
    scaled = c.get("apply_mla_qkv_lora_rescale") and "rescale" not in mutate
    out["a_q"] = math.sqrt(c["hidden_size"] / out["q_lora_rank"]) \
        if scaled else 1.0
    out["a_kv"] = math.sqrt(c["hidden_size"] / out["kv_lora_rank"]) \
        if scaled else 1.0
    return out


def rope(x, theta, interleave=False, start=0):
    """``reference/deepseek_v3.py``'s ``rope`` (half-split here) for rows at
    positions ``start .. start + s - 1``: x ``(s, heads, d)``."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (start + jnp.arange(s)).astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def layer_norm(x, w, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def select(ops, y, c_q, w, c, z, mutate, block):
    """The indexer: ``(ids (s, k), n_valid (s,))``, row t's ``k`` keys of
    largest ``I(t, .)`` among ``s <= t`` (the first ``n_valid`` of them
    are real), a block of query rows at a time."""
    s = y.shape[0]
    n, d, r = c["index_n_heads"], c["index_head_dim"], z["qk_rope_head_dim"]
    topk = c["index_topk"] // (2 if "topk" in mutate else 1)
    k = min(topk, s)
    key = layer_norm(ops.mm(y, w["idx_k_proj"]),
                     w["idx_k_norm"].astype(jnp.float32),
                     w["idx_k_norm.bias"].astype(jnp.float32))
    key = jnp.concatenate(
        [rope(key[:, None, :r], z["theta"], False)[:, 0], key[:, r:]], -1)
    wts = ops.mm(y, w["idx_w_proj"]) / math.sqrt(n * d)          # (s, n)

    def rows(lo, m):
        # a block's queries are projected here (all rows' at once are 1 GB
        # at 34k tokens)
        q = ops.mm(jax.lax.dynamic_slice_in_dim(c_q, lo, m),
                   w["idx_q_proj"]).reshape(m, n, d)
        q = jnp.concatenate([rope(q[..., :r], z["theta"], False, lo),
                             q[..., r:]], -1)
        dots = ops.einsum("shd,td->sht", q, key)
        if "relu" not in mutate:
            dots = jnp.maximum(dots, 0.0)
        score = jnp.einsum("sht,sh->st", dots,
                           jax.lax.dynamic_slice_in_dim(wts, lo, m))
        seen = jnp.arange(s)[None, :] <= lo + jnp.arange(m)[:, None]
        return jax.lax.top_k(jnp.where(seen, score, -jnp.inf), k)[1]

    if s <= block:
        ids = rows(0, s)
    else:
        ids = jax.lax.map(lambda lo: rows(lo, block),
                          jnp.arange(0, s, block)).reshape(s, k)
    return ids, jnp.minimum(jnp.arange(s) + 1, k)


def attention(ops, y, w, c, kind, mutate, block, head_group, sel=None):
    """(s, hidden) -> ``(out (s, hidden), selection)`` of a layer of
    ``kind``; ``w`` holds the layer's weights under their short names,
    Linear weights stored (in, out). ``sel``: a selection to use instead of
    the layer's own (the ``reuse_selection`` mutation)."""
    s = y.shape[0]
    z = sizes(c, kind, mutate)
    heads, rank = z["num_attention_heads"], z["kv_lora_rank"]
    dn, dr, dv = z["qk_nope_head_dim"], z["qk_rope_head_dim"], z["v_head_dim"]
    eps, f32 = c["rms_norm_eps"], jnp.float32
    c_q = z["a_q"] * rms_norm(ops.mm(y, w["q_a_proj"]),
                              w["q_a_layernorm"].astype(f32), eps)
    kva = ops.mm(y, w["kv_a_proj_with_mqa"])
    c_kv = z["a_kv"] * rms_norm(kva[:, :rank],
                                w["kv_a_layernorm"].astype(f32), eps)
    k_r = rope(kva[:, None, rank:], z["theta"], False)[:, 0]
    gate = jnp.ones((s, heads), f32) if "gate" in mutate else \
        jax.nn.sigmoid(ops.mm(y, w["head_gate"]))
    window = None
    if kind == WINDOW:
        window = int(c["sliding_window_size"])
        if "window" in mutate:
            window = (window + 1) // 2
    elif "indexer" in mutate:
        sel = None
    elif sel is None:
        sel = select(ops, y, c_q, w, c, z, mutate, block)
    hg = min(head_group, heads)
    groups = heads // hg

    def by_group(m, width):
        """(in, heads * width) -> (groups, in, hg * width), as stored (a
        group's slice is cast where it is used)."""
        return jnp.moveaxis(m.reshape(m.shape[0], groups, hg * width), 1, 0)

    def group(wq, wkv, wo, g):
        q = ops.mm(c_q, wq).reshape(s, hg, dn + dr)
        q_n, q_r = q[..., :dn], rope(q[..., dn:], z["theta"], False)
        kv = ops.mm(c_kv, wkv).reshape(s, hg, dn + dv)
        k_n, v = kv[..., :dn], kv[..., dn:]

        def rows(lo, n):
            """Query rows lo..lo+n against the keys they can see."""
            span = s if window is None else min(s, n + window - 1)
            start = 0 if span == s else jnp.clip(lo - (window - 1), 0,
                                                 s - span)
            kn, kr, vv = (jax.lax.dynamic_slice_in_dim(a, start, span)
                          for a in (k_n, k_r, v))
            scores = (ops.einsum("shd,thd->hst",
                                 jax.lax.dynamic_slice_in_dim(q_n, lo, n), kn)
                      + ops.einsum("shd,td->hst",
                                   jax.lax.dynamic_slice_in_dim(q_r, lo, n),
                                   kr)) / math.sqrt(dn + dr)
            i = lo + jnp.arange(n)[:, None]
            j = start + jnp.arange(span)[None, :]
            seen = j <= i
            if window is not None:
                seen = seen & (i - j < window)
            if sel is not None:
                ids = jax.lax.dynamic_slice_in_dim(sel[0], lo, n)
                valid = jnp.arange(ids.shape[1])[None] < \
                    jax.lax.dynamic_slice_in_dim(sel[1], lo, n)[:, None]
                seen = seen & jnp.zeros((n, s), bool).at[
                    jnp.arange(n)[:, None], ids].max(valid)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
            return ops.einsum("hst,thd->shd", probs, vv)

        if s <= block:
            o = rows(0, s)
        else:                   # blocks of query rows; s is whole blocks
            o = jax.lax.map(lambda lo: rows(lo, block),
                            jnp.arange(0, s, block)).reshape(s, hg, dv)
        return ops.mm((o * g[:, :, None]).reshape(s, hg * dv), wo)

    parts = (by_group(w["q_b_proj"], dn + dr), by_group(w["kv_b_proj"],
                                                        dn + dv),
             w["o_proj"].reshape(groups, hg * dv, -1),
             jnp.moveaxis(gate.reshape(s, groups, hg), 1, 0))
    out, _ = jax.lax.scan(lambda acc, a: (acc + group(*a), None),
                          jnp.zeros((s, w["o_proj"].shape[1]), f32), parts)
    return out, sel


def _attention_layer(x, w, sel, c, kind, mutate, dt, block, head_group):
    c = dict(c, layer_types=())
    y = rms_norm(x, w["input_layernorm"].astype(jnp.float32),
                 c["rms_norm_eps"])
    out, sel = attention(_Ops(dt), y, w, c, kind, mutate, block, head_group,
                         sel)
    return x + out, sel


ATTN_KEYS = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
             "kv_a_layernorm", "kv_b_proj", "o_proj", "head_gate",
             "idx_q_proj", "idx_k_proj", "idx_k_norm", "idx_k_norm.bias",
             "idx_w_proj", "input_layernorm")


def router_config(c: dict) -> dict:
    """The keys ``reference/deepseek_v3.py``'s router reads."""
    return {"num_experts_per_tok": c["num_experts_per_tok"],
            "scoring_func": c.get("scoring_func", "sigmoid"),
            "n_group": 1, "topk_group": 1,
            "norm_topk_prob": c.get("norm_topk_prob", True),
            "routed_scaling_factor": float(c.get("routed_scaling_factor",
                                                 1.0))}


def forward(params: dict, c: dict, ids, *, forced_picks=None,
            experts_held=None, logits_at=None, mutate=(), matmul_dtype=None,
            block: int = 512, head_group: int = 128, selections=None):
    """One sequence of token ids -> ``(logits, picks)``: float32 logits
    ``(s, vocab)`` (or ``(len(logits_at), vocab)``) and the routed choice
    of every expert layer, ``(expert_layers, s, k)`` int32. A sequence
    longer than ``block`` is padded at its END to whole blocks (causal: no
    real position sees the pad). ``experts_held`` defaults to the
    configuration's own share. ``selections``, a list, receives every full
    layer's ``(ids (s, k), n_valid (s,))`` as numpy arrays."""
    eps, dt, mutate = c["rms_norm_eps"], matmul_dtype, tuple(mutate)
    held = experts_held or c.get("experts_held")
    route_c = router_config(c)
    expert_mutate = tuple(m for m in mutate if m == "shared")
    ids = np.asarray(ids)
    s = len(ids)
    if s > block:
        ids = np.concatenate([ids, np.zeros(-s % block, ids.dtype)])
    if forced_picks is not None:
        forced_picks = np.asarray(forced_picks)
        forced_picks = np.concatenate([forced_picks, np.zeros(
            forced_picks.shape[:1] + (len(ids) - s,)
            + forced_picks.shape[2:], forced_picks.dtype)], 1)
    picks, prev = [], None
    norm = _program(_norm, eps)
    kinds = layer_kinds(c)
    static = _static(c)
    x = jnp.asarray(params["model.embed_tokens.weight"])[ids] \
        .astype(jnp.float32)
    for i in range(c["num_hidden_layers"]):
        w = layer_weights(params, i)
        reuse = prev if ("reuse_selection" in mutate and kinds[i] == FULL
                         and i == 1) else None
        x, sel = _program(_attention_layer, static, kinds[i], mutate, dt,
                          block, head_group)(
            x, {k: w[k] for k in ATTN_KEYS if k in w}, reuse)
        if kinds[i] == FULL and sel is not None:
            prev = sel if "reuse_selection" in mutate else None
            if selections is not None:
                selections.append((np.asarray(sel[0])[:s],
                                   np.asarray(sel[1])[:s]))
        del sel
        # the feed-forward half, FFN_ROWS rows at a time (row by row the
        # same arithmetic)
        dense = i < c["first_k_dense_replace"]
        forced = None if dense or forced_picks is None \
            else forced_picks[len(picks)]
        rows, idx = [], []
        for lo in range(0, len(ids), FFN_ROWS):
            xb = jax.lax.dynamic_slice_in_dim(
                x, lo, min(FFN_ROWS, len(ids) - lo))
            y = norm(xb, w["post_attention_layernorm"])
            if dense:
                out = _program(_swiglu, dt)(y, w["gate_proj"], w["up_proj"],
                                            w["down_proj"])
            else:
                first, count = held or (0, w["gate_proj"].shape[0])
                out, picked = experts(
                    y, w, route_c, expert_mutate, None if forced is None
                    else forced[lo:lo + FFN_ROWS], (first, count), dt)
                idx.append(np.asarray(picked))
            rows.append(xb + out)
        del x, xb, y, out
        x = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
        del rows
        if idx:
            picks.append(np.concatenate(idx)[:s])
    x = norm(x, params["model.norm.weight"])[:s]
    if logits_at is not None:
        x = x[np.asarray(logits_at)]
    head = params["lm_head.weight"]
    # vocabulary blocks: a block of the head is cast to float32 where it is
    # used (the whole head at once is 3.1 GB, and "highest" splits it again)
    cols = max(1, min(HEAD_COLS, 2 ** 25 // max(1, x.shape[0])))
    mm = _program(_matmul, dt)
    logits = jnp.concatenate(
        [mm(x, head[:, lo:lo + cols])
         for lo in range(0, head.shape[1], cols)], -1)
    return logits, (np.stack(picks) if picks else None)


def selected_share(got, want, rows=None) -> float:
    """Share of the reference's selected set that the system selected, over
    the full layers and ``rows`` (all rows by default). ``got`` / ``want``:
    per full layer ``(ids (s, k), n_valid (s,))``."""
    hit = total = 0
    for (gi, gn), (wi, wn) in zip(got, want):
        for r in (range(len(wn)) if rows is None else rows):
            ref = set(wi[r, :wn[r]].tolist())
            hit += len(ref & set(gi[r, :gn[r]].tolist()))
            total += len(ref)
    return hit / max(1, total)


def compare(got_logits, got_picks, got_sel, params, c, ids, logits_at=None,
            sel_rows=None, own_routing=True, **kw) -> dict:
    """The comparison of a system's logits (at ``logits_at``, or
    everywhere), picks and selections on ``ids`` with the reference:
    ``{"picks_agree", "logits_err", "selected_share"}`` - (a) each routing
    for itself, (b) the reference held to the system's expert picks, each
    indexer selecting for itself (``own_routing=False`` skips (a): one
    forward). ``got_sel`` holds the system's
    selections at ``sel_rows`` (row r of it is sequence row
    ``sel_rows[r]``), or at every row."""
    got_picks = np.asarray(got_picks)
    agree = float("nan")
    if own_routing:
        _, own = forward(params, c, ids, logits_at=[0], **kw)
        agree = float(np.mean([
            len(set(a) & set(b)) / len(a)
            for a, b in zip(got_picks.reshape(-1, got_picks.shape[-1]),
                            own.reshape(-1, own.shape[-1]))]))
    ref_sel = []
    want, _ = forward(params, c, ids, forced_picks=got_picks,
                      logits_at=logits_at, selections=ref_sel, **kw)
    if sel_rows is not None:
        ref_sel = [(i[np.asarray(sel_rows)], n[np.asarray(sel_rows)])
                   for i, n in ref_sel]
    got = np.asarray(got_logits, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.sqrt(np.mean((got - want) ** 2))
                / np.sqrt(np.mean(want ** 2)))
    share = selected_share(got_sel, ref_sel) if ref_sel else float("nan")
    return {"picks_agree": agree, "logits_err": err, "selected_share": share}


def _np_sel(selections, rows=None):
    out = []
    for ids, n in selections:
        ids, n = np.asarray(ids._value)[0], np.asarray(n._value)[0]
        out.append((ids, n) if rows is None else (ids[rows], n[rows]))
    return out


def model_outputs(model, ids, at=None):
    """The model's own forward (its dtype, one jitted ``EvalStep``) on one
    sequence, no cache: ``(logits (s, vocab), picks, selections)``; only
    the logits of the rows ``at`` leave the program where given (4,096 rows
    of a 152k vocabulary are 1.2 GB)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import EvalStep

    def fn(m, b):
        logits, picks, sel = m(b["ids"], output_router_picks=True,
                               output_selections=True)
        return logits._value[0, b["at"]._value], picks, sel

    ids = np.asarray(ids)
    rows = np.arange(len(ids)) if at is None else np.asarray(at)
    logits, picks, sel = EvalStep(model, fn)({
        "ids": paddle.to_tensor(ids[None]),
        "at": paddle.to_tensor(rows.astype(np.int32))})
    return getattr(logits, "_value", logits), np.asarray(picks._value), \
        _np_sel(sel)


def cached_outputs(model, ids, *, chunk: int, decode: int, block: int = 16,
                   table_len: int = 0):
    """``ids`` through the model's CACHE path as the paged engine drives
    it (``reference/mimo_v2.py``'s, for this model's two groups): prefill
    in chunks of ``chunk`` through a fresh cache whose window table is a
    ring, then the last ``decode`` tokens one at a time. ``table_len``: the
    positions the table spans (the deployment's ``max_len``, so that the
    scores, the exact top-k over them and the rows' gather have the widths
    the engine's programs have; the sequence's own length by default).
    Returns ``(rows, logits (len(rows), vocab), picks, selections at
    rows)``: each chunk's first and last real token and every decode
    step."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import EvalStep
    ids = np.asarray(ids, np.int32)
    n, window = len(ids), int(model.config.sliding_window_size)
    row, fb, wb = ring_tables(max(n + chunk, table_len), block, window,
                              chunk)
    cache = model.init_paged_kv_cache(fb, block, window_blocks=wb)

    def fn(m, b):
        logits, new_cache, picks, sel = m(
            b["ids"], cache=b["cache"], block_table=b["table"],
            pos=b["pos"], output_router_picks=True, output_selections=True)
        at = b["at"]._value
        return logits._value[0, at], new_cache, picks, \
            [(i._value[:, at], v._value[:, at]) for i, v in sel]

    step = EvalStep(model, fn)
    table = paddle.to_tensor(row[None])
    rows, logits, picks, done = [], [], [], 0
    sel_ids, sel_n = None, None         # per full layer: a list a step
    while done < n:
        prefill = done < n - decode
        m = min(chunk, n - decode - done) if prefill else 1
        padded = np.zeros((1, chunk if prefill else 1), np.int32)
        padded[0, :m] = ids[done:done + m]
        lg, cache, pk, sel = step({
            "ids": paddle.to_tensor(padded), "cache": cache,
            "table": table, "pos": paddle.to_tensor(
                np.asarray([done], np.int32)),
            "at": paddle.to_tensor(np.asarray([0, m - 1], np.int32))})
        ends = sorted({0, m - 1})
        rows += [done + e for e in ends]
        logits += list(np.asarray(lg._value, np.float32)[:len(ends)])
        picks.append(np.asarray(pk._value)[:, :m])
        if sel_ids is None:
            sel_ids, sel_n = [[] for _ in sel], [[] for _ in sel]
        for layer, (i, v) in enumerate(sel):
            sel_ids[layer].append(
                np.asarray(getattr(i, "_value", i))[0, :len(ends)])
            sel_n[layer].append(
                np.asarray(getattr(v, "_value", v))[0, :len(ends)])
        done += m
    # a decode step's selection is as wide as the table, a chunk's
    # index_topk: pad to the widest (only the first n_valid ids count)
    width = max((a.shape[1] for layer in sel_ids or [] for a in layer),
                default=0)
    sels = [(np.concatenate([np.pad(a, ((0, 0), (0, width - a.shape[1])))
                             for a in layer_ids]), np.concatenate(layer_n))
            for layer_ids, layer_n in zip(sel_ids or [], sel_n or [])]
    return np.asarray(rows), np.stack(logits), np.concatenate(picks, 1), sels


def timed_context(model, params, c, prompt, tokens, *, chunk: int,
                  table_len: int, past: int, decode: int = TIMED_DECODE,
                  say=print, got=None, **kw) -> dict:
    """The check at the context the cell is TIMED at, on a sequence the
    timed path produced: ``prompt + tokens`` of a request that completed
    inside the window, teacher-forced through the model's cache path at
    the deployment's chunk and table width (the scores over ``table_len``
    positions, the exact top-k over them, the selected rows' gather and
    both groups' reads, s > 1 and s = 1, at 16 times ``index_topk``), and
    ONE forward of the reference over the same sequence, held to the
    system's expert picks, its indexer selecting for itself. Returns

      ``logits_err``      relative rms of the logits at the rows of the
                          cache path past position ``past`` (the shared
                          document's length: the question's chunk ends and
                          the last ``decode`` tokens, one step each)
      ``selected_share``  the share of the reference's selected set, at
                          those rows, that the system selected
      ``below_max``, ``below_mean``, ``same_argmax``  the distance of each
                          EMITTED token's reference logit below the
                          reference's largest at its position, in that
                          position's standard deviations (what the engine
                          itself chose, 64 slots at a time)

    ``got``: the cache path's outputs where the caller has them already
    (``cached_outputs`` of ``(prompt + tokens)[:-1]``). ``kw``: the
    reference's blocks, ``mutate``, ``matmul_dtype``."""
    prompt = np.asarray(prompt, np.int32)
    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    rows, got, got_picks, got_sel = got or cached_outputs(
        model, seq[:-1], chunk=chunk, decode=decode, table_len=table_len)
    late = np.flatnonzero(rows >= past)
    emit = np.arange(len(prompt) - 1, len(seq) - 1)         # rows that emit
    ref_sel = []
    want, _ = forward(params, c, seq[:-1], forced_picks=got_picks,
                      logits_at=np.concatenate([rows[late], emit]),
                      selections=ref_sel, **kw)
    want = np.asarray(want, np.float32)
    at_rows, at_emit = want[:len(late)], want[len(late):]
    err = float(np.sqrt(np.mean((got[late] - at_rows) ** 2))
                / np.sqrt(np.mean(at_rows ** 2)))
    share = selected_share([(i[late], n[late]) for i, n in got_sel],
                           [(i[rows[late]], n[rows[late]])
                            for i, n in ref_sel]) if ref_sel else float("nan")
    chosen = at_emit[np.arange(len(emit)), seq[len(prompt):]]
    below = (at_emit.max(-1) - chosen) / at_emit.std(-1)
    out = {"tokens": len(seq), "rows": len(late), "logits_err": err,
           "selected_share": share, "below_max": float(below.max()),
           "below_mean": float(below.mean()),
           "same_argmax": float(np.mean(below == 0))}
    say(f"  timed context: {len(prompt)} prompt + {len(tokens)} emitted "
        f"tokens through the cache path ({len(rows)} rows, {len(late)} past "
        f"{past}): logits relative rms {err:.5f}, selected share "
        f"{share:.5f}; emitted logit below the reference's largest: max "
        f"{out['below_max']:.3f} sd, mean {out['below_mean']:.3f} sd, the "
        f"reference's own argmax at {out['same_argmax']:.3f} of positions")
    return out


def check(model, ctx) -> dict:
    """The set-up checks for ``correct``. (a): one seeded ``SEQ``-token
    sequence (twice ``index_topk``) through the model's own no-cache
    forward and through the reference. (b): a seeded context of
    ``check_context`` tokens prefilled in the deployment's chunks through
    a fresh cache of both groups (the ring wraps, the selection drops most
    of the context) and then 8 tokens decoded one at a time, against the
    reference's full forward at every chunk's first and last token and
    every decode step: the logits, the routers' picks, and the share of
    the reference's selected set the program selected."""
    c = ctx.config
    dep = c.get("deployment", {})
    n = min(int(dep.get("check_tokens", SEQ)), c["max_position_embeddings"])
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 37])
    ids = rng.integers(0, c["vocab_size"], n, np.int32)
    kw = dict(block=int(dep.get("check_block", 256)),
              head_group=int(dep.get("check_head_group", 32)))
    # every eighth row's logits (and the last): 513 rows; the picks and the
    # selections of EVERY row are compared
    at = np.unique(np.append(np.arange(7, n, 8), n - 1))
    logits, picks, sel = model_outputs(model, ids, at=at)
    params = {k: p._value for k, p in model.named_parameters()}
    r = compare(logits, picks, sel, params, c, ids, logits_at=at, **kw)
    del logits
    long_n = int(dep.get("check_context", 2 * SEQ))
    chunk = int(c.get("overrides", {}).get("prefill_chunk", {}).get(
        "value", 32))
    long_ids = rng.integers(0, c["vocab_size"], long_n, np.int32)
    # the table as wide as the deployment's, so that this check and the
    # timed context's run the SAME two programs, at the engine's widths
    rows, got, got_picks, got_sel = cached_outputs(
        model, long_ids, chunk=chunk, decode=8,
        table_len=int(dep.get("max_len", 0)))
    rc = compare(got, got_picks, got_sel, params, c, long_ids,
                 logits_at=rows, sel_rows=rows, **kw)
    ok = lambda e: bool(np.isfinite(e) and e <= LOGITS_TOLERANCE)  # noqa: E731
    return {
        f"(a) no-cache forward vs the plain float32 reference on {n} seeded "
        f"tokens ({len(at)} rows' logits): relative rms error {r['logits_err']:.4f} <= "
        f"{LOGITS_TOLERANCE} (reference held to the model's expert picks), "
        f"picks agree {r['picks_agree']:.4f} >= {PICKS_TOLERANCE}, selected "
        f"share {r['selected_share']:.4f} >= {SELECTED_FLOOR}":
        ok(r["logits_err"]) and r["picks_agree"] >= PICKS_TOLERANCE
        and r["selected_share"] >= SELECTED_FLOOR,
        f"(b) through both groups' caches, {long_n} tokens in chunks of "
        f"{chunk} then 8 decode steps, {len(rows)} positions (each chunk's "
        f"first and last token, each decode step): logits relative rms "
        f"error {rc['logits_err']:.4f} <= {LOGITS_TOLERANCE}, picks agree "
        f"{rc['picks_agree']:.4f} >= {PICKS_TOLERANCE}, selected share "
        f"{rc['selected_share']:.4f} >= {SELECTED_FLOOR}":
        ok(rc["logits_err"]) and rc["picks_agree"] >= PICKS_TOLERANCE
        and rc["selected_share"] >= SELECTED_FLOOR}
