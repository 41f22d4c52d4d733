"""The plain reference for Laguna-class configurations.

A straightforward float32 ``jax.numpy`` forward of the published block
(``model_type: laguna``): no kernel, no cache, no batching,
``default_matmul_precision("highest")``. One sequence at a time:

  embedding; per layer  x += Attn_l(RMSNorm(x));  x += FFN_l(RMSNorm(x));
  final RMSNorm; untied head.   RMSNorm eps ``rms_norm_eps``; no biases.

  Layer l is FULL where ``layer_types[l]`` is ``full_attention``, SLIDING
  where it is ``sliding_attention``; H_l = num_attention_heads_per_layer[l].
  Attn: q = y W_q -> (H_l, d); k = y W_k, v = y W_v -> (kvh, d), d =
        head_dim, kvh = num_key_value_heads: H_l / kvh queries a key.
        RoPE half-split (rotate_half) on the first d * partial_rotary_factor
        dims of the kind's ``rope_parameters``, the rest pass:
        FULL, ``rope_type: yarn`` (transformers' _compute_yarn_parameters):
          dim = 64; inv_extra_i = base^(-2i/dim); inv_inter_i =
          inv_extra_i / factor; corr(b) = dim ln(orig / (2 pi b)) /
          (2 ln base); low = floor(corr(beta_fast)), high =
          ceil(corr(beta_slow)), clamped to [0, dim - 1]; ramp_i =
          clamp((i - low) / (high - low), 0, 1); inv_freq_i = inv_inter_i
          ramp_i + inv_extra_i (1 - ramp_i); cos and sin times
          ``attention_factor``, on q and on k.
        SLIDING: plain RoPE at its base on all d dims.
        score(t, s, h) = q_h . k_(h // (H_l / kvh)) / sqrt(d), causal; in a
        sliding layer s is seen from t iff 0 <= t - s < sliding_window.
        o_h = sum_s softmax(score) v;  o_h <- sigmoid(y W_g)_h o_h
        (``gating_types: per_head``);  out = concat(o) W_o.
  FFN where ``mlp_layer_types[l]`` is dense: SwiGLU(intermediate_size).
  FFN elsewhere: p = softmax(y W_r) over the router's 256, in float32;
        picks = the top ``num_experts_per_tok`` of p; w = p[picks] /
        sum p[picks] (``norm_topk_prob``) * ``moe_routed_scaling_factor``;
        no selection bias, no softcap, weights on the outputs;
        y_out = sum_e w_e SwiGLU_e(y) + SwiGLU_shared(y).

`assumed` (the config gives no key; the configuration file lists each):
the router's scores are a softmax over all experts (``scoring_func``,
written into the file as a key of its own); the shared expert has no gate;
the per-head gate reads the attention's normed input through its own
matrix; the window counts the token itself; no QK-norm; RoPE pairs are
half-split over the turned dims.

Departures from a literal transcription, none of them in the mathematics:
attention is evaluated in blocks of query rows (each against every key
under the mask), so that 10k tokens fit; the experts are evaluated one at
a time, each over every row weighted by the row's routing weight for it
(``reference/deepseek_v3.py``'s ``experts`` and ``route``, shared: softmax
scores, the top-k, renormalised, scaled, the shared expert added once);
the router's selection bias is the model's own zeros, which move neither a
pick nor a weight; ``logits_at`` limits the head to the positions asked
for. The angles of RoPE are computed in float64 from the positions.

``experts_held=(first, count)`` gives the reference a chip's share: only
those global experts add to the result; the router keeps its width.
``forced_picks`` holds the reference to the system's routing (top-k is
discontinuous: see ``reference/deepseek_v3.py``). ``mutate`` breaks the
reference on purpose, one published term at a time, and ``matmul_dtype``
rounds every product's operands through a coarser dtype: the tests and the
limits are set by showing that each of these fails the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v3 import (_Ops, _matmul, _norm, _program,
                                             _swiglu, experts, layer_weights,
                                             rms_norm)
from benchmark.reference.mimo_v2 import (cached_outputs, model_outputs,
                                         ring_tables)

__all__ = ["forward", "compare", "check", "model_outputs", "cached_outputs",
           "ring_tables", "yarn_range", "inv_freq"]

# The comparison's limits (``compare``), each set between two readings on
# the chip at the published widths (PERF.md section 6, PR 39: round 2's
# call, ``tools/limits_probe.py --workload laguna-repo-agent-decode --seeds
# 3100000007`` and the cell's five runs at seeds 3300000013-3700000031).
#
# (b), (d) relative rms error of the logits, the reference held to the
# system's picks. The bf16 model reads 0.0107-0.0108 without a cache and
# 0.0100-0.0105 through both groups' caches at 8,704 tokens (the reference
# in bf16: 0.0116 / 0.0110); the reference in 8-bit floats reads 0.158 /
# 0.154, the system with its window ring two blocks short 0.0213, YaRN
# without its attention factor 0.093 (cached), without YaRN 0.102. A
# window of 511 / 513 reads 0.0113 (one key of 512, under bf16's rounding:
# no limit sees it; the short ring is the window's control).
LOGITS_TOLERANCE = 0.015
# (a), (c) share of (token, layer, k) picks on which system and reference
# agree when each routes for itself: top-10 of 256 softmax scores. The bf16
# model 0.984-0.990, the reference in 8-bit floats 0.897 / 0.909.
PICKS_TOLERANCE = 0.95
SEQ = 256
# one published term a name: RoPE without YaRN on full layers (plain, at
# the full layers' base and dims), YaRN without its attention factor, no
# per-head gate, a window of 511 / 513, sigmoid router scores, no 2.5, no
# shared expert
MUTATIONS = ("yarn_plain", "yarn_factor", "gate", "window_minus",
             "window_plus", "sigmoid", "scaling", "shared")
FULL, SLIDING = "full_attention", "sliding_attention"


def yarn_range(rp: dict, dim: int):
    """``(low, high)`` of the YaRN ramp over ``dim`` turned dims."""
    base, orig = float(rp["rope_theta"]), rp["original_max_position_embeddings"]

    def corr(rotations):
        return dim * math.log(orig / (2 * math.pi * rotations)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), dim - 1)
    return low, high


def inv_freq(rp: dict, dim: int, mutate=()):
    """float64 ``(dim // 2,)`` frequencies and the factor on cos and sin
    of a kind's ``rope_parameters`` over ``dim`` turned dims."""
    base = float(rp["rope_theta"])
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", "default") != "yarn" or "yarn_plain" in mutate:
        return extra, 1.0
    low, high = yarn_range(rp, dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    inv = extra / float(rp["factor"]) * ramp + extra * (1.0 - ramp)
    factor = 1.0 if "yarn_factor" in mutate else float(
        rp.get("attention_factor", 0.1 * math.log(rp["factor"]) + 1.0))
    return inv, factor


def rope_tables(s: int, rp: dict, head_dim: int, mutate=()):
    """``(cos, sin)`` float32 ``(s, 1, rot)`` at positions 0..s-1, the
    attention factor in them, and ``rot``."""
    rot = int(head_dim * rp.get("partial_rotary_factor", 1.0)) // 2 * 2
    inv, factor = inv_freq(rp, rot, mutate)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    ang = np.concatenate([ang, ang], -1)[:, None]
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32), rot)


def turn(x, cos, sin, rot):
    """rotate-half on ``x[..., :rot]`` (s, heads, d); the rest pass."""
    xr = x[..., :rot]
    turned = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + turned * sin, x[..., rot:]], -1)


def attention(ops, y, w, cos, sin, heads, kvh, d, rot, window, mutate,
              block):
    """(s, hidden) -> (s, hidden) of one layer; ``w`` holds the layer's
    weights under their short names, Linear weights stored (in, out)."""
    s = y.shape[0]
    g = heads // kvh
    q = turn(ops.mm(y, w["q_proj"]).reshape(s, heads, d), cos, sin, rot)
    k = turn(ops.mm(y, w["k_proj"]).reshape(s, kvh, d), cos, sin, rot)
    v = ops.mm(y, w["v_proj"]).reshape(s, kvh, d)

    def rows(lo, n):
        """Query rows lo..lo+n against every key, masked by position."""
        qb = jax.lax.dynamic_slice_in_dim(q, lo, n).reshape(n, kvh, g, d)
        scores = ops.einsum("skgd,tkd->kgst", qb, k) / math.sqrt(d)
        i, j = lo + jnp.arange(n)[:, None], jnp.arange(s)[None, :]
        seen = j <= i if window is None else (j <= i) & (i - j < window)
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), -1)
        return ops.einsum("kgst,tkd->skgd", probs, v).reshape(n, heads, d)

    if s <= block:
        out = rows(0, s)
    else:                       # blocks of query rows; s is whole blocks
        out = jax.lax.map(lambda lo: rows(lo, block),
                          jnp.arange(0, s, block)).reshape(s, heads, d)
    if "gate" not in mutate:
        out = out * jax.nn.sigmoid(ops.mm(y, w["head_gate"]))[..., None]
    return ops.mm(out.reshape(s, heads * d), w["o_proj"])


def _attention_layer(x, w, cos, sin, eps, heads, kvh, d, rot, window,
                     mutate, dt, block):
    y = rms_norm(x, w["input_layernorm"].astype(jnp.float32), eps)
    return x + attention(_Ops(dt), y, w, cos, sin, heads, kvh, d, rot,
                         window, mutate, block)


ATTN_KEYS = ("q_proj", "k_proj", "v_proj", "o_proj", "head_gate",
             "input_layernorm")


def router_config(c: dict, mutate=()) -> dict:
    """The keys ``reference/deepseek_v3.py``'s router reads, from this
    family's."""
    return {"num_experts_per_tok": c["num_experts_per_tok"],
            "scoring_func": "sigmoid" if "sigmoid" in mutate
            else c.get("scoring_func", "softmax"),
            "n_group": 1, "topk_group": 1,
            "norm_topk_prob": c.get("norm_topk_prob", True),
            "routed_scaling_factor": c["moe_routed_scaling_factor"]}


def forward(params: dict, c: dict, ids, *, forced_picks=None,
            experts_held=None, logits_at=None, mutate=(), matmul_dtype=None,
            block: int = 512):
    """One sequence of token ids -> ``(logits, picks)``: float32 logits
    ``(s, vocab)`` (or ``(len(logits_at), vocab)``) and the routed choice
    of every expert layer, ``(expert_layers, s, k)`` int32. A sequence
    longer than ``block`` is padded at its END to whole blocks (causal: no
    real position sees the pad), so that sequences share programs.
    ``experts_held`` defaults to the configuration's own share."""
    eps, dt, mutate = c["rms_norm_eps"], matmul_dtype, tuple(mutate)
    held = experts_held or c.get("experts_held")
    route_c = router_config(c, mutate)
    expert_mutate = tuple(m for m in mutate if m in ("scaling", "shared"))
    ids = np.asarray(ids)
    s = len(ids)
    if s > block:
        ids = np.concatenate([ids, np.zeros(-s % block, ids.dtype)])
    if forced_picks is not None:
        forced_picks = np.asarray(forced_picks)
        forced_picks = np.concatenate([forced_picks, np.zeros(
            forced_picks.shape[:1] + (len(ids) - s,)
            + forced_picks.shape[2:], forced_picks.dtype)], 1)
    n = c["num_hidden_layers"]
    d, kvh = c["head_dim"], c["num_key_value_heads"]
    tables = {kind: rope_tables(len(ids), c["rope_parameters"][kind], d,
                                mutate) for kind in (FULL, SLIDING)}
    window = int(c["sliding_window"]) - ("window_minus" in mutate) \
        + ("window_plus" in mutate)
    picks = []
    norm = _program(_norm, eps)
    x = jnp.asarray(params["model.embed_tokens.weight"])[ids] \
        .astype(jnp.float32)
    for i in range(n):
        w = layer_weights(params, i)
        kind = c["layer_types"][i]
        cos, sin, rot = tables[kind]
        attn = _program(_attention_layer, eps,
                        int(c["num_attention_heads_per_layer"][i]), kvh, d,
                        rot, window if kind == SLIDING else None, mutate, dt,
                        block)
        x = attn(x, {k: w[k] for k in ATTN_KEYS if k in w}, cos, sin)
        y = norm(x, w["post_attention_layernorm"])
        if c["mlp_layer_types"][i] == "dense":
            x = x + _program(_swiglu, dt)(y, w["gate_proj"], w["up_proj"],
                                          w["down_proj"])
        else:
            first, count = held or (0, w["gate_proj"].shape[0])
            out, idx = experts(
                y, w, route_c, expert_mutate, None if forced_picks is None
                else forced_picks[len(picks)], (first, count), dt)
            picks.append(np.asarray(idx)[:s])
            x = x + out
    x = norm(x, params["model.norm.weight"])[:s]
    if logits_at is not None:
        x = x[np.asarray(logits_at)]
    head = params["lm_head.weight"]
    cols = max(1, 2 ** 25 // max(1, x.shape[0]))       # vocabulary blocks
    mm = _program(_matmul, dt)
    logits = jnp.concatenate(
        [mm(x, head[:, lo:lo + cols])
         for lo in range(0, head.shape[1], cols)], -1)
    return logits, (np.stack(picks) if picks else None)


def compare(got_logits, got_picks, params, c, ids, logits_at=None,
            **kw) -> dict:
    """The two-part comparison of a system's logits (at ``logits_at``, or
    everywhere) and picks on ``ids`` with the reference: ``{"picks_agree":
    share, "logits_err": relative rms}`` — (a) each routing for itself,
    (b) the reference held to the system's picks."""
    got_picks = np.asarray(got_picks)
    _, own = forward(params, c, ids, logits_at=[0], **kw)
    agree = float(np.mean([
        len(set(a) & set(b)) / len(a)
        for a, b in zip(got_picks.reshape(-1, got_picks.shape[-1]),
                        own.reshape(-1, own.shape[-1]))]))
    want, _ = forward(params, c, ids, forced_picks=got_picks,
                      logits_at=logits_at, **kw)
    got = np.asarray(got_logits, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.sqrt(np.mean((got - want) ** 2))
                / np.sqrt(np.mean(want ** 2)))
    return {"picks_agree": agree, "logits_err": err}


def check(model, ctx) -> dict:
    """The set-up checks for ``correct``. (a), (b): one seeded ``SEQ``-token
    sequence through the model's own forward and through the reference.
    (c), (d): a seeded context of ``check_context`` tokens (the
    configuration's: past YaRN's ``original_max_position_embeddings``, so
    the interpolated frequencies are on the checked path, and many windows
    and rings long) prefilled in the deployment's chunks and then decoded
    through BOTH groups' caches, against the reference's full forward, at
    every chunk's first and last token and every decode step."""
    c = ctx.config
    n = min(SEQ, c["max_position_embeddings"])
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 23])
    ids = rng.integers(0, c["vocab_size"], n, np.int32)
    logits, picks = model_outputs(model, ids)
    params = {k: p._value for k, p in model.named_parameters()}
    r = compare(logits, picks, params, c, ids)
    del logits
    dep = c.get("deployment", {})
    long_n = int(dep.get("check_context", 4 * SEQ))
    chunk = int(c.get("overrides", {}).get("prefill_chunk", {}).get(
        "value", 32))
    long_ids = rng.integers(0, c["vocab_size"], long_n, np.int32)
    rows, got, got_picks = cached_outputs(model, long_ids, chunk=chunk,
                                          decode=8)
    # query blocks of 256 at 8,704 tokens: a sliding layer's scores are
    # then 0.6 GB a block, and the check's peak stays under the serving
    # path's
    rc = compare(got, got_picks, params, c, long_ids, logits_at=rows,
                 block=256)
    ok = lambda e: bool(np.isfinite(e) and e <= LOGITS_TOLERANCE)  # noqa: E731
    return {
        f"(a) routed picks, model vs the plain float32 reference, each "
        f"routing for itself on {n} seeded tokens: {r['picks_agree']:.4f} "
        f">= {PICKS_TOLERANCE}": r["picks_agree"] >= PICKS_TOLERANCE,
        f"(b) model logits vs the reference held to the model's picks: "
        f"relative rms error {r['logits_err']:.4f} <= {LOGITS_TOLERANCE}":
        ok(r["logits_err"]),
        f"(c) routed picks through both groups' caches, {long_n} tokens in "
        f"chunks of {chunk} then 8 decode steps: {rc['picks_agree']:.4f} "
        f">= {PICKS_TOLERANCE}": rc["picks_agree"] >= PICKS_TOLERANCE,
        f"(d) cached logits at {len(rows)} positions (each chunk's first "
        f"and last token, each decode step, the last past "
        f"{long_n - 9}) vs the reference's full forward held to the "
        f"model's picks: relative rms error {rc['logits_err']:.4f} <= "
        f"{LOGITS_TOLERANCE}": ok(rc["logits_err"])}
