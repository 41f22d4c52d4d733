"""The plain reference for Ouro-class (looped) configurations.

A straightforward float32 ``jax.numpy`` forward of the published block
(``model_type: ouro``; arXiv 2510.25741): no kernel, no cache, no batching,
``default_matmul_precision("highest")``. One sequence at a time:

  x <- E[ids]
  for u in 0 .. total_ut_steps - 1:            (the SAME weights every pass)
    for l in 0 .. num_hidden_layers - 1:
      x <- x + RMSNorm(Attn_l(RMSNorm(x; a1_l)); a2_l)
      x <- x + RMSNorm(SwiGLU_l(RMSNorm(x; m1_l)); m2_l)
    x <- RMSNorm(x; g);  h_u = x               (the one final norm, in the loop)
  Attn_l: q, k, v = y Wq, y Wk, y Wv -> (T, heads, head_dim) each; RoPE,
        half-split rotate_half, on all head_dim dims, base ``rope_theta``, at
        the token's index (the same in every pass); softmax(q k^T /
        sqrt(head_dim)), causal, full; (P v) Wo. In pass u the keys and
        values are those of pass u.
  SwiGLU_l: (silu(y Wg) * (y Wu)) Wd.
  Exit gate: lambda_u = sigmoid(h_u . w + b); p_0 = lambda_0, p_u = lambda_u
        prod_{j<u} (1 - lambda_j), the last pass takes the rest. A token's
        logits are those of the first pass whose cumulative p reaches
        ``early_exit_threshold``, else the last: logits = h_exit W_head.
  RMSNorm eps ``rms_norm_eps``; no bias on a projection; the gate has one.

`assumed` (from the model's public modeling code and paper, not a key of
the catalog row): the four norms a layer, the final norm inside the loop,
the exit rule. Departures from a literal transcription, none of them in
the mathematics: attention is evaluated in blocks of query rows (each
against every key under the mask); ``logits_at`` limits the head to the
positions asked for; a layer's weights are cast to float32 a layer at a
time.

``mutate`` breaks the reference on purpose, one published term at a time,
and ``matmul_dtype`` rounds every product's operands through a coarser
dtype: the tests and the limits are set by showing that each fails.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v3 import (_Ops, _matmul, _norm, _program,
                                             _static, layer_weights,
                                             rms_norm)
from benchmark.reference.llama_like import rope

# The comparison's limits, each set between two readings on the chip at the
# published sizes (PERF.md section 6, PR 33; tools/looped_limits_probe.py;
# the configuration's output norms drawn at N(0, 0.1), which its file
# explains: at unit output norms the same readings ran from 0.007 to 0.198
# by the seed and no limit could be set).
#
# Relative rms error of the logits. The bf16 model reads 0.0215-0.0231
# without a cache and 0.0197-0.0224 through every pass's slice of the paged
# cache (12 seeds each; mean 0.0220, sd 0.0005; 192 layer passes of bf16:
# the random walk of ``llama_like.py``'s docstring, 2**-9 x sqrt(10 x 192) =
# 8.6%, is an outer figure, because each half's output is normed before it
# joins the residual). The nearest broken variant is the reference in 8-bit
# floats, 0.190-0.193; then one pass fewer 0.364-0.384, no norm between
# passes 0.489-0.518, a pass reading another pass's keys and values
# 0.635-0.713 (through the cache too), the SYSTEM broken, every pass through
# one slice of the arenas, 0.671-0.733, a half's output norm dropped
# 1.34-1.38 (2 seeds each). The limit is over twice the largest reading
# and under a third of the least any broken variant read.
LOGITS_TOLERANCE = 0.05
# Largest |difference| of the exit distribution p (a probability) at any
# position and pass: a maximum over a thousand entries, so it reads higher
# than an rms would. bf16: 0.0066-0.0213 (mean 0.014, sd 0.004 over 26
# readings). Broken: 8-bit floats 0.097-0.111, no norm between passes
# 0.103-0.187, the rest 0.18-0.90; a pass reading the pass before through
# the cache reads 0.070-0.110 here and fails by its logits. The limit is
# 2.3 times the largest reading and half the least of 8-bit floats.
EXIT_TOLERANCE = 0.05
SEQ = 256
CONTEXT = 448
DECODE = 8
# one published term a name: one pass fewer; pass u reading the keys and
# values of pass u - 1 / of the last pass (the KV sharing the paper
# evaluates as an approximation); no final norm between passes; the
# attention half's / the SwiGLU half's output norm dropped
MUTATIONS = ("three_passes", "kv_prev_pass", "kv_last_pass", "no_loop_norm",
             "attn_out_norm", "mlp_out_norm")


def _layer(x, w, kv, c, mutate, dt, block):
    """One sandwich layer on ``(s, hidden)`` float32. ``kv`` replaces the
    layer's own keys and values where given. Returns ``(x, (k, v))``."""
    c, ops = dict(c), _Ops(dt)
    eps, heads = c["rms_norm_eps"], c["num_attention_heads"]
    kvh, s = c["num_key_value_heads"], x.shape[0]
    d = c.get("head_dim") or c["hidden_size"] // heads
    f32 = lambda name: w[name].astype(jnp.float32)              # noqa: E731
    y = rms_norm(x, f32("input_layernorm"), eps)
    theta = float(c["rope_theta"])
    q = rope(ops.mm(y, w["q_proj"]).reshape(s, heads, d), theta)
    own = (rope(ops.mm(y, w["k_proj"]).reshape(s, kvh, d), theta),
           ops.mm(y, w["v_proj"]).reshape(s, kvh, d))
    k, v = own if kv is None else kv
    g = heads // kvh

    def rows(lo, n):
        """Query rows lo..lo+n against every key, causal by position."""
        qb = jax.lax.dynamic_slice_in_dim(q, lo, n).reshape(n, kvh, g, d)
        scores = ops.einsum("skgd,tkd->kgst", qb, k) / math.sqrt(d)
        seen = jnp.arange(s)[None, :] <= lo + jnp.arange(n)[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), -1)
        return ops.einsum("kgst,tkd->skgd", probs, v).reshape(n, heads * d)

    if s <= block:
        a = rows(0, s)
    else:                       # blocks of query rows; s is whole blocks
        a = jax.lax.map(lambda lo: rows(lo, block),
                        jnp.arange(0, s, block)).reshape(s, heads * d)
    a = ops.mm(a, w["o_proj"])
    if "attn_out_norm" not in mutate:
        a = rms_norm(a, f32("input_layernorm_2"), eps)
    x = x + a
    y = rms_norm(x, f32("post_attention_layernorm"), eps)
    m = ops.mm(jax.nn.silu(ops.mm(y, w["gate_proj"]))
               * ops.mm(y, w["up_proj"]), w["down_proj"])
    if "mlp_out_norm" not in mutate:
        m = rms_norm(m, f32("post_attention_layernorm_2"), eps)
    return x + m, own


def _gate(h, w, b):
    return jax.nn.sigmoid(h @ w.astype(jnp.float32)[:, 0]
                          + b.astype(jnp.float32)[0])


def exit_distribution(lam: np.ndarray) -> np.ndarray:
    """``lam (U, s)`` -> ``p (s, U)``."""
    u = len(lam)
    p, stay = np.zeros((lam.shape[1], u), np.float64), 1.0
    for i in range(u):
        p[:, i] = stay if i == u - 1 else lam[i] * stay
        stay = stay * (1.0 - lam[i])
    return p.astype(np.float32)


def forward(params: dict, c: dict, ids, *, logits_at=None, mutate=(),
            matmul_dtype=None, block: int = 512, _kv_from=None):
    """One sequence of token ids -> ``(logits, p)``: float32 logits ``(s,
    vocab)`` (or ``(len(logits_at), vocab)``) and the exit distribution ``p
    (s, total_ut_steps)``. A sequence longer than ``block`` is padded at its
    END to whole blocks (causal: no real position sees the pad), so that
    sequences share programs."""
    mutate, dt = tuple(mutate), matmul_dtype
    if "kv_last_pass" in mutate and _kv_from is None:
        # the unbroken forward's last pass gives every pass its keys and
        # values (what a cache without per-pass slices would hold)
        kept = []
        forward(params, c, ids, logits_at=[0], matmul_dtype=dt, block=block,
                _kv_from=kept)
        _kv_from = kept
    ids = np.asarray(ids)
    s = len(ids)
    if s > block:
        ids = np.concatenate([ids, np.zeros(-s % block, ids.dtype)])
    passes = c["total_ut_steps"] - ("three_passes" in mutate)
    n_layers = c["num_hidden_layers"]
    layer = _program(_layer, _static(c), mutate, dt, block)
    norm = _program(_norm, c["rms_norm_eps"])
    x = jnp.asarray(params["model.embed_tokens.weight"])[ids] \
        .astype(jnp.float32)
    lam, hs, prev = [], [], [None] * n_layers
    for u in range(passes):
        for i in range(n_layers):
            kv = None
            if "kv_last_pass" in mutate:
                kv = _kv_from[i]
            elif "kv_prev_pass" in mutate:
                kv = prev[i]
            x, own = layer(x, layer_weights(params, i), kv)
            prev[i] = own
            if isinstance(_kv_from, list) and "kv_last_pass" not in mutate \
                    and u == passes - 1:
                _kv_from.append(own)
        h = norm(x, params["model.norm.weight"])
        if "no_loop_norm" not in mutate:
            x = h
        hs.append(h)
        lam.append(np.asarray(_program(_gate)(
            h, params["model.early_exit_gate.weight"],
            params["model.early_exit_gate.bias"])))
    p = exit_distribution(np.stack(lam))[:s]
    p = np.pad(p, ((0, 0), (0, c["total_ut_steps"] - passes)))
    # the first pass whose cumulative p reaches the threshold, else the last
    reached = np.cumsum(p, -1) >= float(c["early_exit_threshold"])
    exit_at = np.where(reached.any(-1), reached.argmax(-1), passes - 1)
    at = np.arange(s) if logits_at is None else np.asarray(logits_at)
    h = jnp.stack(hs)[exit_at[at], at]
    head = params["lm_head.weight"]
    cols = max(1, 2 ** 25 // max(1, h.shape[0]))       # vocabulary blocks
    mm = _program(_matmul, dt)
    logits = jnp.concatenate(
        [mm(h, head[:, lo:lo + cols])
         for lo in range(0, head.shape[1], cols)], -1)
    return logits, p


def compare(got_logits, got_p, params, c, ids, logits_at=None, **kw) -> dict:
    """A system's logits and exit distribution (at ``logits_at``, or
    everywhere) on ``ids`` against the reference's: ``{"logits_err":
    relative rms, "exit_err": largest |difference| of p}``."""
    want, p = forward(params, c, ids, logits_at=logits_at, **kw)
    if logits_at is not None:
        p = p[np.asarray(logits_at)]
    got = np.asarray(got_logits, np.float32)
    want = np.asarray(want, np.float32)
    return {"logits_err": float(np.sqrt(np.mean((got - want) ** 2))
                                / np.sqrt(np.mean(want ** 2))),
            "exit_err": float(np.abs(np.asarray(got_p, np.float32)
                                     - p).max())}


def model_outputs(model, ids):
    """The model's own forward (its dtype, one jitted ``EvalStep``) on one
    sequence, no cache: ``(logits (s, vocab), p (s, passes))``."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import EvalStep
    step = EvalStep(model, lambda m, b: m(b, output_exit_distribution=True))
    logits, p = step(paddle.to_tensor(np.asarray(ids)[None]))
    return logits._value[0], np.asarray(p._value[0])


def cached_outputs(model, ids, *, chunk: int, decode: int, block: int = 16):
    """``ids`` through the model's CACHE path as the paged engine drives
    it, in the model's own dtype and kernels: prefill in chunks of ``chunk``
    (the last one right-padded, as the engine pads it) through a fresh
    paged cache, every pass through its own slice, then the last ``decode``
    tokens one at a time. Returns ``(rows, logits (len(rows), vocab), p
    (len(rows), passes))`` at each chunk's first and last real token and
    every decode step."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import EvalStep
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    cols = -(-(n + chunk) // block)
    cache = model.init_paged_kv_cache(cols + 1, block)

    def fn(m, b):
        logits, new_cache, p = m(
            b["ids"], cache=b["cache"], block_table=b["table"], pos=b["pos"],
            output_exit_distribution=True)
        at = b["at"]._value
        return logits._value[0, at], new_cache, p._value[0, at]

    step = EvalStep(model, fn)
    table = paddle.to_tensor(1 + np.arange(cols, dtype=np.int32)[None])
    rows, logits, exits, done = [], [], [], 0
    while done < n:
        prefill = done < n - decode
        m = min(chunk, n - decode - done) if prefill else 1
        padded = np.zeros((1, chunk if prefill else 1), np.int32)
        padded[0, :m] = ids[done:done + m]
        lg, cache, p = step({
            "ids": paddle.to_tensor(padded), "cache": cache, "table": table,
            "pos": paddle.to_tensor(np.asarray([done], np.int32)),
            "at": paddle.to_tensor(np.asarray([0, m - 1], np.int32))})
        ends = sorted({0, m - 1})
        rows += [done + e for e in ends]
        logits += list(np.asarray(lg._value, np.float32)[:len(ends)])
        exits += list(np.asarray(p._value, np.float32)[:len(ends)])
        done += m
    return np.asarray(rows), np.stack(logits), np.stack(exits)


def check(model, ctx) -> dict:
    """The set-up checks for ``correct``. (a): one seeded ``SEQ``-token
    sequence through the model's own forward and through the reference.
    (b): a seeded context of ``CONTEXT`` tokens prefilled in the
    deployment's chunk through a fresh paged cache, then ``DECODE`` tokens
    decoded one at a time, against the reference's full forward at each
    chunk's first and last token and every decode step. (c): the exit
    distribution at the same positions."""
    from paddle_tpu.serving.paging import default_prefill_chunk
    c = ctx.config
    n = min(SEQ, c["max_position_embeddings"])
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 23])
    ids = rng.integers(0, c["vocab_size"], n, np.int32)
    params = {k: p._value for k, p in model.named_parameters()}
    r = compare(*model_outputs(model, ids), params, c, ids)
    dep = c.get("deployment", {})
    long_n = int(dep.get("check_context", CONTEXT)) + DECODE
    chunk = int(c.get("overrides", {}).get("prefill_chunk", {}).get(
        "value", default_prefill_chunk(16, int(dep.get("max_len", 1024)))))
    long_ids = rng.integers(0, c["vocab_size"], long_n, np.int32)
    rows, got, got_p = cached_outputs(model, long_ids, chunk=chunk,
                                      decode=DECODE)
    rc = compare(got, got_p, params, c, long_ids, logits_at=rows)
    ok = lambda e, limit: bool(np.isfinite(e) and e <= limit)  # noqa: E731
    return {
        f"(a) model logits vs the plain float32 reference on {n} seeded "
        f"tokens, all {c['total_ut_steps']} passes: relative rms error "
        f"{r['logits_err']:.4f} <= {LOGITS_TOLERANCE}; exit distribution "
        f"within {r['exit_err']:.4f} <= {EXIT_TOLERANCE}":
        ok(r["logits_err"], LOGITS_TOLERANCE)
        and ok(r["exit_err"], EXIT_TOLERANCE),
        f"(b) cached logits, {long_n - DECODE} tokens in chunks of {chunk} "
        f"then {DECODE} decode steps through every pass's slice, at "
        f"{len(rows)} positions vs the reference's full forward: relative "
        f"rms error {rc['logits_err']:.4f} <= {LOGITS_TOLERANCE}":
        ok(rc["logits_err"], LOGITS_TOLERANCE),
        f"(c) the exit distribution at those positions: within "
        f"{rc['exit_err']:.4f} <= {EXIT_TOLERANCE}":
        ok(rc["exit_err"], EXIT_TOLERANCE)}
