"""The plain reference for LFM2-MoE-class configurations.

A straightforward float32 ``jax.numpy`` forward of the published block
(``model_type: lfm2_moe``): no kernel, no cache, no batching,
``default_matmul_precision("highest")``. One sequence at a time:

  embedding; per layer  x += Mixer_l(RMSNorm(x));  x += FFN_l(RMSNorm(x));
  final RMSNorm (``embedding_norm``); untied head. RMSNorm eps ``norm_eps``;
  no biases.

  Mixer where ``layer_types[l]`` is ``conv`` (gated short conv, L =
        ``conv_L_cache`` = 3, no conv bias): [B | C | x~] = y W_in (hidden
        -> 3 hidden); u_t = B_t x~_t; z_t = w_0 u_(t-2) + w_1 u_(t-1) +
        w_2 u_t, a direct sum of the taps over the WHOLE sequence, one
        filter a channel, u before position 0 taken as 0; out = (C_t z_t)
        W_out.
  Mixer where it is ``full_attention``: q = y W_q -> (H, d), k = y W_k,
        v = y W_v -> (kvh, d), d = hidden / H; q <- RMSNorm_d(q), k <-
        RMSNorm_d(k), a head at a time (``q_layernorm``, ``k_layernorm``);
        RoPE rotate-half over all d at ``rope_theta``; score(t, s, h) = q_h .
        k_(h // (H / kvh)) / sqrt(d), causal; out = concat(softmax(score)
        v) W_o.
  FFN in the first ``num_dense_layers`` layers: SwiGLU(intermediate_size).
  FFN elsewhere: s = sigmoid(y W_r) in float32 over the ``num_experts``;
        picks = the top ``num_experts_per_tok`` of s + expert_bias (the
        layer's ``e_score_correction_bias``: ``use_expert_bias``); w =
        s[picks] / sum s[picks] (``norm_topk_prob``) * routed_scaling_factor;
        y_out = sum_e w_e SwiGLU_e(y); no shared expert.

Departures from a literal transcription, none of them in the mathematics:
attention is evaluated in blocks of query rows (each against every key
under the mask), so that 5k tokens fit; the experts are evaluated one at a
time, each over every row weighted by the row's routing weight for it
(``reference/deepseek_v3.py``'s ``experts`` and ``route``, shared, with
the shared expert left out); the normalisation adds 1e-20 to the sum of
the chosen scores where transformers' ``Lfm2MoeSparseMoeBlock`` adds 1e-6
(as that code was read; the sum of four sigmoid scores is above 0.1 on
these weights, so the two differ by less than 1e-5 of a weight);
``logits_at`` limits the head to the positions asked for. The angles of
RoPE are computed in float64 from the positions.

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
                                             _swiglu, experts, model_outputs,
                                             rms_norm)
from benchmark.reference.laguna import rope_tables, turn

__all__ = ["forward", "compare", "check", "model_outputs", "cached_outputs",
           "greedy"]

# The comparison's limits (``compare``), each set between two readings on
# the chip at the published widths (PERF.md section 6: the cell's
# six runs at seeds 3200000023-3800000083 and ``tools/limits_probe.py
# --workload lfm2-tool-agent-decode --seeds 3400000041``).
#
# (b), (d) relative rms error of the logits, the reference held to the
# system's picks. The bf16 model reads 0.0108 without a cache and
# 0.0102-0.0105 through the paged cache past a prefix hit at 4,096 (the
# reference in bf16: 0.0115 / 0.0108); the reference in 8-bit floats reads
# 0.141 / 0.147, the SYSTEM with the conv state zeroed at the cut 0.138,
# no QK-norm 0.144 / 0.035. The limit is 2.8 times the largest sound
# reading and under a quarter of the 8-bit reference's.
LOGITS_TOLERANCE = 0.03
# (a), (c) share of (token, layer, k) picks on which system and reference
# agree when each routes for itself: top-4 of 64 biased sigmoid scores.
# The bf16 model 0.948-0.973; the reference in 8-bit floats 0.838 / 0.842,
# without the selection bias (drawn at std 0.05) 0.573. With the bias the
# quantiles of N(0, 0.05) in a seed's order (``init_quantiles``): the model
# 0.947-0.970, the 8-bit reference 0.849 / 0.838, no bias 0.562.
PICKS_TOLERANCE = 0.90
SEQ = 256
# one published term a name: no selection bias in the choice, no norm on
# q and k, the conv's taps in reverse order, no C gate on the conv
MUTATIONS = ("bias", "qk_norm", "conv_flip", "conv_gate")
CONV, ATTN = "conv", "full_attention"
BLOCK = 16


def layer_weights(params: dict, i: int) -> dict:
    """Layer ``i``'s weights under short names, from the model's own
    parameter names (``model.layers.<i>.self_attn.q_proj.weight``,
    ``...conv.in_proj.weight``, ``...feed_forward.gate_proj``, ...)."""
    pre = f"model.layers.{i}."
    out = {}
    for name, v in params.items():
        if not name.startswith(pre):
            continue
        key = name[len(pre):]
        key = key[:-len(".weight")] if key.endswith(".weight") else key
        for lead in ("self_attn.", "conv.", "feed_forward."):
            if key.startswith(lead):
                key = key[len(lead):]
        out[key] = v
    return out


def short_conv(ops, y, w, mutate):
    """(s, hidden) -> (s, hidden): the gated short conv of one layer."""
    s, h = y.shape
    taps = w["conv_weight"].astype(jnp.float32)          # (L, hidden)
    if "conv_flip" in mutate:
        taps = taps[::-1]
    bcx = ops.mm(y, w["in_proj"])
    gate_b, gate_c, xt = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    u = gate_b * xt
    n = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, h), u.dtype), u])
    z = sum(taps[k] * padded[k:k + s] for k in range(n))
    out = z if "conv_gate" in mutate else gate_c * z
    return ops.mm(out, w["out_proj"])


def attention(ops, y, w, cos, sin, heads, kvh, d, eps, mutate, block):
    """(s, hidden) -> (s, hidden) of one attention layer."""
    s = y.shape[0]
    g = heads // kvh
    q = ops.mm(y, w["q_proj"]).reshape(s, heads, d)
    k = ops.mm(y, w["k_proj"]).reshape(s, kvh, d)
    v = ops.mm(y, w["v_proj"]).reshape(s, kvh, d)
    if "qk_norm" not in mutate:
        q = rms_norm(q, w["q_layernorm"].astype(jnp.float32), eps)
        k = rms_norm(k, w["k_layernorm"].astype(jnp.float32), eps)
    q, k = turn(q, cos, sin, d), turn(k, cos, sin, d)

    def rows(lo, n):
        """Query rows lo..lo+n against every key, causal by position."""
        qb = jax.lax.dynamic_slice_in_dim(q, lo, n).reshape(n, kvh, g, d)
        scores = ops.einsum("skgd,tkd->kgst", qb, k) / math.sqrt(d)
        seen = jnp.arange(s)[None, :] <= lo + jnp.arange(n)[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), -1)
        return ops.einsum("kgst,tkd->skgd", probs, v).reshape(n, heads, d)

    if s <= block:
        out = rows(0, s)
    else:                       # blocks of query rows; s is whole blocks
        out = jax.lax.map(lambda lo: rows(lo, block),
                          jnp.arange(0, s, block)).reshape(s, heads, d)
    return ops.mm(out.reshape(s, heads * d), w["out_proj"])


def _mixer_layer(x, w, cos, sin, kind, eps, heads, kvh, d, mutate, dt,
                 block):
    y = rms_norm(x, w["operator_norm"].astype(jnp.float32), eps)
    if kind == CONV:
        return x + short_conv(_Ops(dt), y, w, mutate)
    return x + attention(_Ops(dt), y, w, cos, sin, heads, kvh, d, eps,
                         mutate, block)


MIXER_KEYS = ("operator_norm", "in_proj", "conv_weight", "out_proj",
              "q_proj", "k_proj", "v_proj", "q_layernorm", "k_layernorm")


def router_config(c: dict) -> dict:
    """The keys ``reference/deepseek_v3.py``'s router reads, from this
    family's."""
    return {"num_experts_per_tok": c["num_experts_per_tok"],
            "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": c.get("norm_topk_prob", True),
            "routed_scaling_factor": c.get("routed_scaling_factor", 1.0)}


def forward(params: dict, c: dict, ids, *, forced_picks=None,
            logits_at=None, mutate=(), matmul_dtype=None, block: int = 512):
    """One sequence of token ids -> ``(logits, picks)``: float32 logits
    ``(s, vocab)`` (or ``(len(logits_at), vocab)``) and the routed choice
    of every expert layer, ``(expert_layers, s, k)`` int32. A sequence
    longer than ``block`` is padded at its END to whole blocks (causal: no
    real position sees the pad), so that sequences share programs."""
    eps, dt, mutate = c["norm_eps"], matmul_dtype, tuple(mutate)
    route_c = router_config(c)
    # this family has no shared expert
    expert_mutate = ("shared",) + tuple(m for m in mutate if m == "bias")
    ids = np.asarray(ids)
    s = len(ids)
    if s > block:
        ids = np.concatenate([ids, np.zeros(-s % block, ids.dtype)])
    if forced_picks is not None:
        forced_picks = np.asarray(forced_picks)
        forced_picks = np.concatenate([forced_picks, np.zeros(
            forced_picks.shape[:1] + (len(ids) - s,)
            + forced_picks.shape[2:], forced_picks.dtype)], 1)
    heads, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // heads
    cos, sin, _ = rope_tables(len(ids), c["rope_parameters"], d)
    picks = []
    norm = _program(_norm, eps)
    x = jnp.asarray(params["model.embed_tokens.weight"])[ids] \
        .astype(jnp.float32)
    for i in range(c["num_hidden_layers"]):
        w = layer_weights(params, i)
        kind = c["layer_types"][i]
        mixer = _program(_mixer_layer, kind, eps, heads, kvh, d, mutate, dt,
                         block)
        x = mixer(x, {k: w[k] for k in MIXER_KEYS if k in w}, cos, sin)
        y = norm(x, w["ffn_norm"])
        if i < c["num_dense_layers"]:
            x = x + _program(_swiglu, dt)(y, w["gate_proj"], w["up_proj"],
                                          w["down_proj"])
        else:
            out, idx = experts(
                y, w, route_c, expert_mutate, None if forced_picks is None
                else forced_picks[len(picks)], (0, c["num_experts"]), dt)
            picks.append(np.asarray(idx)[:s])
            x = x + out
    x = norm(x, params["model.embedding_norm.weight"])[:s]
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


class PagedRun:
    """One slot's walk through the model's PAGED cache as the engine drives
    it, in the model's own dtype and kernels: table row ``1, 2, ...`` (block
    0 the trash block), right-padded chunks with their ``valid_len``, then
    single tokens. ``share(n_blocks)`` starts a second slot whose table
    shares this one's first ``n_blocks`` blocks (a prefix hit) and whose
    own blocks follow them; ``zero_at_cut`` empties the conv entry it reads
    at the cut (the control that must fail)."""

    def __init__(self, model, n_tokens: int, *, block: int = BLOCK,
                 chunk: int = 32):
        import paddle_tpu as paddle
        from paddle_tpu.jit import EvalStep
        self.block, self.chunk = block, chunk
        self.cols = -(-(n_tokens + chunk) // block)
        self.cache = model.init_paged_kv_cache(2 * self.cols + 1, block)
        self.row = 1 + np.arange(self.cols, dtype=np.int32)
        self._tensor = paddle.to_tensor

        def fn(m, b):
            logits, new_cache, picks = m(
                b["ids"], cache=b["cache"], block_table=b["table"],
                pos=b["pos"], valid_len=b["valid"], output_router_picks=True)
            return logits._value[0, b["at"]._value], new_cache, picks
        self._step = EvalStep(model, fn)

    def share(self, n_blocks: int, zero_at_cut: bool = False):
        """The next slot: this one's first ``n_blocks`` blocks, then fresh
        ones."""
        self.row = np.concatenate([
            self.row[:n_blocks],
            self.cols + 1 + np.arange(self.cols - n_blocks, dtype=np.int32)])
        if zero_at_cut:
            leaves = self.cache
            conv = leaves["mixer_state"]._value
            leaves["mixer_state"] = self._tensor(
                conv.at[int(self.row[n_blocks - 1])].set(0))

    def run(self, ids, start: int, at=None):
        """Feed ``ids`` from position ``start``: whole chunks while more than
        one token is left, else one token. Returns ``(rows, logits (rows,
        vocab), picks (layers, len(ids), k))``: each call's first and last
        real token, or the positions ``at`` where given."""
        rows, logits, picks, done = [], [], [], 0
        n = len(ids)
        while done < n:
            m = min(self.chunk, n - done) if n - done > 1 else 1
            width = self.chunk if m > 1 else 1
            feed = np.zeros((1, width), np.int32)
            feed[0, :m] = ids[done:done + m]
            ends = sorted({0, m - 1})
            lg, self.cache, pk = self._step({
                "ids": self._tensor(feed), "cache": self.cache,
                "table": self._tensor(self.row[None]),
                "pos": self._tensor(np.asarray([start + done], np.int32)),
                "valid": self._tensor(np.asarray(m, np.int32)),
                "at": self._tensor(np.asarray(ends, np.int32))})
            rows += [start + done + e for e in ends]
            logits += list(np.asarray(lg._value, np.float32))
            picks.append(np.asarray(pk._value)[:, :m])
            done += m
        return np.asarray(rows), np.stack(logits), np.concatenate(picks, 1)


def cached_outputs(model, ids, *, chunk: int, decode: int, cut: int = 0,
                   block: int = BLOCK, zero_at_cut: bool = False):
    """``ids`` through the model's paged cache as the engine drives it:
    where ``cut`` (whole blocks) is given, the first ``cut`` tokens are one
    request of their own and the rest a SECOND request that shares their
    blocks (a prefix hit) and prefills from the cut; the last ``decode``
    tokens one at a time. Returns ``(rows, logits, picks)`` over the
    second request's positions (each chunk's first and last token and
    every decode step) and every position's picks."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    walk = PagedRun(model, n, block=block, chunk=chunk)
    first_picks = None
    if cut:
        assert cut % block == 0
        _, _, first_picks = walk.run(ids[:cut], 0)
        walk.share(cut // block, zero_at_cut)
    r1, l1, p1 = walk.run(ids[cut:n - decode], cut)
    r2, l2, p2 = [], [], []
    for t in range(n - decode, n):
        r, lg, pk = walk.run(ids[t:t + 1], t)
        r2.append(r[:1])
        l2.append(lg[:1])
        p2.append(pk)
    picks = np.concatenate(([first_picks] if cut else []) + [p1] + p2, 1)
    return (np.concatenate([r1] + r2), np.concatenate([l1] + l2), picks)


def greedy(model, ids, *, chunk: int, steps: int, block: int = BLOCK,
           cut: int = 0, zero_at_cut: bool = False):
    """``ids`` prefilled through a fresh paged cache in chunks of
    ``chunk`` (past a prefix hit at ``cut``, as ``cached_outputs`` drives
    it), then ``steps`` tokens emitted greedily one at a time through it.
    Returns the emitted ids."""
    ids = np.asarray(ids, np.int32)
    walk = PagedRun(model, len(ids) + steps, block=block, chunk=chunk)
    if cut:
        walk.run(ids[:cut], 0)
        walk.share(cut // block, zero_at_cut)
    _, lg, _ = walk.run(ids[cut:], cut)
    emitted = [int(np.argmax(lg[-1]))]
    while len(emitted) < steps:
        _, lg, _ = walk.run(np.asarray(emitted[-1:], np.int32),
                            len(ids) + len(emitted) - 1)
        emitted.append(int(np.argmax(lg[-1])))
    return np.asarray(emitted, np.int32)


def check(model, ctx) -> dict:
    """The set-up checks for ``correct``. (a), (b): one seeded ``SEQ``-token
    sequence through the model's own forward and through the reference.
    (c), (d): a seeded context of ``check_context`` tokens: its first
    ``check_cut`` tokens served as a request of their own, the rest by a
    second request that HITS them (it shares their blocks, conv state
    included) and prefills from the cut in the deployment's chunks, then 8
    decode steps; against the reference's full forward at every chunk's
    first and last token and every decode step."""
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
    cut = int(dep.get("check_cut", 2 * SEQ))
    chunk = int(c.get("overrides", {}).get("prefill_chunk", {}).get(
        "value", 32))
    long_ids = rng.integers(0, c["vocab_size"], long_n, np.int32)
    rows, got, got_picks = cached_outputs(model, long_ids, chunk=chunk,
                                          decode=8, cut=cut)
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
        f"(c) routed picks through the paged cache, {long_n} tokens, a "
        f"prefix hit at {cut}, chunks of {chunk}, then 8 decode steps: "
        f"{rc['picks_agree']:.4f} >= {PICKS_TOLERANCE}":
        rc["picks_agree"] >= PICKS_TOLERANCE,
        f"(d) cached logits at {len(rows)} positions past the prefix hit "
        f"(each chunk's first and last token, each decode step) vs the "
        f"reference's full forward held to the model's picks: relative "
        f"rms error {rc['logits_err']:.4f} <= {LOGITS_TOLERANCE}":
        ok(rc["logits_err"])}
