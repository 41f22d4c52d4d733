"""The plain reference for DeepSeek-V3-class configurations.

A straightforward float32 ``jax.numpy`` forward of the published block
(``model_type: deepseek_v3``): no kernel, no cache, no batching,
``default_matmul_precision("highest")`` (a float32 product on a TPU runs in
bf16 passes without it). One sequence at a time:

  embedding; per layer  x += Attn(RMSNorm(x));  x += FFN(RMSNorm(x));
  final RMSNorm; untied head.

  Attn: q = y W_q (or through q_lora) -> heads x (nope | rope);
        [c_kv | k_pe] = y W_kva; c_kv <- RMSNorm(c_kv); k_pe ONE shared head;
        RoPE(q_pe), RoPE(k_pe): theta, and with ``rope_interleave`` the
        pairs de-interleaved (even | odd) before rotate-half, as the public
        implementation does; [k_nope | v] = c_kv W_kvb;
        scores = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope);
        causal softmax; out = (P v) W_o.   No rope_scaling: no extra scale.
  FFN, layers below ``first_k_dense_replace``: SwiGLU(intermediate_size).
  FFN, other layers: s = sigmoid(y W_g) in float32; pick the top-k of
        s + b (b = e_score_correction_bias) inside the ``topk_group`` best
        of ``n_group`` groups (a group's score: its two best s + b);
        w = s[idx] (without b); w <- w / (sum w + 1e-20) if
        ``norm_topk_prob``; w <- routed_scaling_factor * w;
        y_out = sum_e w_e SwiGLU_e(y) + SwiGLU_shared(y)
        (shared width n_shared_experts * moe_intermediate_size).

Departures from a literal transcription, none of them in the mathematics:
attention is evaluated in blocks of query rows (``block``, each against
every key under the causal mask), so that long sequences fit; the experts are evaluated ONE AT A TIME, each over every
row and weighted by the row's routing weight for it (zero where the row did
not choose it: no gather, no scatter, one program for all experts), so that
a layer fits beside the model under test (one expert layer of the kanana
configuration is 2.56 GB in float32); weights are the model's own
values, cast to float32 where they are used; ``logits_at`` limits the head
to the positions asked for, and the head runs in blocks of the vocabulary.

Top-k is discontinuous: two correct implementations in different
precisions choose different experts for some tokens, after which their
logits differ by the experts' size and not by rounding. So ``forward``
takes ``forced_picks``, the system's own choice, and the comparison is in
two parts (``compare``): (a) the share of picks on which the two agree
when each routes for itself, (b) logits with the reference held to the
system's picks.

``experts_held=(first, count)`` gives the reference a chip's share: only
those global experts add to the result (the guide's section-4 cut); the
router keeps its width. ``mutate`` breaks the reference on purpose, one
published term at a time, and ``matmul_dtype`` rounds every product's
operands through a coarser dtype: the tests and the limits are set by
showing that each of these fails the comparison.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# The comparison's limits (``compare``), each with its reason; the two
# readings they were set between are in PERF.md section 6 (PR 27).
#
# (b) relative rms error of the logits, reference forced to the system's
# picks. bf16 rounds at 2**-9 relative; a layer has about fourteen rounded
# products on the residual path (five projections and two attention
# products, the router's inputs, three expert and three shared-expert
# products), adding like a random walk over L layers to about 2**-9 *
# sqrt(14 L): 2.1% at 8 layers. Three times that passes bf16 on every seed
# read and fails an 8-bit float (2**-4 a rounding: tens of percent) or any
# dropped term (each moves the logits by a large share of their own size).
LOGITS_TOLERANCE = 0.06
# (a) share of (token, layer, k) picks on which system and reference agree
# when each routes for itself. A pick flips where two biased scores lie
# closer than the rounding of the router's input (about 2**-8 of a score
# of order 1, against a typical gap between the k-th and (k+1)-th of 128
# scores of order 1e-2), and a flipped layer perturbs the next: a few
# percent at 8 layers in bf16. A dropped selection bias flips about a
# fifth of the picks where the bias is drawn at std 0.05.
PICKS_TOLERANCE = 0.90
SEQ = 256
MUTATIONS = ("bias", "norm_topk", "scaling", "shared", "k_pe", "latent_norm")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta, interleave):
    """x: (s, heads, d) at positions 0..s-1."""
    s, _, d = x.shape
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


class _Ops:
    """The matmul every product goes through: float32, operands rounded
    through ``matmul_dtype`` first where one is given."""

    def __init__(self, matmul_dtype=None):
        self.dt = matmul_dtype

    def r(self, a):
        a = jnp.asarray(a).astype(jnp.float32)
        return a if self.dt is None else a.astype(self.dt).astype(jnp.float32)

    def mm(self, a, b):
        return self.r(a) @ self.r(b)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.r(a), self.r(b))


def swiglu(ops, x, w_gate, w_up, w_down):
    return ops.mm(jax.nn.silu(ops.mm(x, w_gate)) * ops.mm(x, w_up), w_down)


def attention(ops, y, w, c, mutate, block):
    """(s, hidden) -> (s, hidden); ``w`` holds this layer's weights under
    their short names, Linear weights stored (in, out)."""
    s = y.shape[0]
    heads, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope_d, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                        c["v_head_dim"])
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    inter = bool(c.get("rope_interleave", True))
    if c.get("q_lora_rank") is None:
        q = ops.mm(y, w["q_proj"])
    else:
        q = ops.mm(rms_norm(ops.mm(y, w["q_a_proj"]),
                            w["q_a_layernorm"].astype(jnp.float32), eps),
                   w["q_b_proj"])
    q = q.reshape(s, heads, nope + rope_d)
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], theta, inter)
    kva = ops.mm(y, w["kv_a_proj_with_mqa"])
    c_kv, k_pe = kva[:, :rank], rope(kva[:, None, rank:], theta, inter)[:, 0]
    if "latent_norm" not in mutate:
        c_kv = rms_norm(c_kv, w["kv_a_layernorm"].astype(jnp.float32), eps)
    kv = ops.mm(c_kv, w["kv_b_proj"]).reshape(s, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    def rows(lo, n):
        """Query rows lo..lo+n against every key, causal by position."""
        scores = ops.einsum("shd,thd->hst",
                            jax.lax.dynamic_slice_in_dim(q_nope, lo, n),
                            k_nope)
        if "k_pe" not in mutate:
            scores = scores + ops.einsum(
                "shd,td->hst", jax.lax.dynamic_slice_in_dim(q_pe, lo, n),
                k_pe)
        scores = scores / math.sqrt(nope + rope_d)
        causal = jnp.arange(s)[None, :] <= lo + jnp.arange(n)[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return ops.einsum("hst,thd->shd", probs, v).reshape(n, heads * vd)

    if s <= block:
        out = rows(0, s)
    else:                       # blocks of query rows; s is whole blocks
        out = jax.lax.map(lambda lo: rows(lo, block),
                          jnp.arange(0, s, block)).reshape(s, heads * vd)
    return ops.mm(out, w["o_proj"])


def route(logits, bias, c, mutate, forced=None):
    """float32 ``logits (s, E)`` -> ``(idx (s, k), weights (s, k))``."""
    k, e = c["num_experts_per_tok"], logits.shape[1]
    scores = jax.nn.sigmoid(logits) if c.get("scoring_func", "sigmoid") \
        == "sigmoid" else jax.nn.softmax(logits, -1)
    choice = scores if "bias" in mutate else scores + bias
    g = int(c.get("n_group", 1))
    if g > 1:
        grouped = choice.reshape(-1, g, e // g)
        best2 = jnp.sort(grouped, -1)[..., -2:].sum(-1)        # (s, g)
        keep = jnp.argsort(-best2, -1)[:, :int(c["topk_group"])]
        mask = jnp.zeros_like(best2, bool).at[
            jnp.arange(len(best2))[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(mask, e // g, 1), choice, 0.0)
    idx = jnp.argsort(-choice, -1, stable=True)[:, :k] if forced is None \
        else jnp.asarray(forced)
    wts = jnp.take_along_axis(scores, idx, -1)
    if c.get("norm_topk_prob", True) and "norm_topk" not in mutate:
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    if "scaling" not in mutate:
        wts = wts * float(c["routed_scaling_factor"])
    return idx, wts


_PROGRAMS = {}


def _program(fn, *static):
    """``fn(*arrays, *static)`` as ONE compiled program per ``static``.
    The functions above stay plain; this only keeps a chip from compiling
    every operation of every layer by itself (an eager float32 forward is
    some thousands of small programs)."""
    key = (fn, static)
    if key not in _PROGRAMS:
        def run(*arrays):
            with jax.default_matmul_precision("highest"):
                return fn(*arrays, *static)
        _PROGRAMS[key] = jax.jit(run)
    return _PROGRAMS[key]


def _static(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items() if isinstance(
        v, (int, float, str, bool, type(None)))))


def _attention_layer(x, w, c, mutate, dt, block):
    c = dict(c)
    y = rms_norm(x, w["input_layernorm"].astype(jnp.float32),
                 c["rms_norm_eps"])
    return x + attention(_Ops(dt), y, w, c, mutate, block)


def _norm(x, w, eps):
    return rms_norm(x, w.astype(jnp.float32), eps)


def _route(y, gate, bias, forced, c, mutate):
    logits = y @ gate.astype(jnp.float32)           # the router: float32
    return route(logits, bias.astype(jnp.float32), dict(c), mutate, forced)


def _one_expert(y, share, w_gate, w_up, w_down, e, dt):
    """Expert ``e`` (an index into the stacked weights held) over every
    row, each weighted by its routing weight for this expert (zero where
    the row did not choose it)."""
    return swiglu(_Ops(dt), y, w_gate[e], w_up[e], w_down[e]) \
        * share[:, None]


def _matmul(a, b, dt):
    return _Ops(dt).mm(a, b)


def _swiglu(y, w_gate, w_up, w_down, dt):
    return swiglu(_Ops(dt), y, w_gate, w_up, w_down)


def experts(y, w, c, mutate=(), forced=None, held=None, dt=None):
    """The routed experts, one at a time, plus the shared experts. Returns ``(out (s, hidden), idx (s, k))``."""
    idx, wts = _program(_route, _static(c), tuple(mutate))(
        y, w["gate"], w["e_score_correction_bias"],
        None if forced is None else jnp.asarray(forced, jnp.int32))
    idx_np, wts_np = np.asarray(idx), np.asarray(wts)
    first, count = held or (0, c["n_routed_experts"])
    # (s, experts held): a row's routing weight for each expert held, zero
    # where it did not choose it (a pick of an expert not held adds nothing)
    share = np.zeros((len(idx_np), count), np.float32)
    local = idx_np - first
    tok, slot = np.nonzero((local >= 0) & (local < count))
    share[tok, local[tok, slot]] = wts_np[tok, slot]
    one = _program(_one_expert, dt)
    out = jnp.zeros_like(y)
    for e in np.nonzero(share.any(0))[0]:       # the experts somebody chose
        out = out + one(y, share[:, e], w["gate_proj"], w["up_proj"],
                        w["down_proj"], np.int32(e))
    if "shared" not in mutate:
        out = out + _program(_swiglu, dt)(
            y, w["shared_experts.gate_proj"], w["shared_experts.up_proj"],
            w["shared_experts.down_proj"])
    return out, idx


ATTN_KEYS = ("q_proj", "q_a_proj", "q_a_layernorm", "q_b_proj",
             "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj", "o_proj")


def layer_weights(params: dict, i: int) -> dict:
    """Layer ``i``'s weights under short names, from the model's own
    parameter names (``model.layers.<i>.self_attn.q_proj.weight``, ...;
    the stacked experts are ``model.layers.<i>.mlp.gate_proj`` etc.)."""
    pre = f"model.layers.{i}."
    out = {}
    for name, v in params.items():
        if not name.startswith(pre):
            continue
        key = name[len(pre):]
        key = key[:-len(".weight")] if key.endswith(".weight") else key
        for lead in ("self_attn.", "mlp."):
            if key.startswith(lead):
                key = key[len(lead):]
        out[key] = v
    return out


def forward(params: dict, c: dict, ids, *, forced_picks=None,
            experts_held=None, logits_at=None, mutate=(), matmul_dtype=None,
            block: int = 512):
    """One sequence of token ids -> ``(logits, picks)``: float32 logits
    ``(s, vocab)`` (or ``(len(logits_at), vocab)``) and the routed choice
    of every expert layer, ``(expert_layers, s, k)`` int32. A sequence
    longer than ``block`` is padded at its END to whole blocks (causal: no
    real position sees the pad), so that sequences share programs."""
    eps, dt, mutate = c["rms_norm_eps"], matmul_dtype, tuple(mutate)
    ids = np.asarray(ids)
    s = len(ids)
    if s > block:
        ids = np.concatenate([ids, np.zeros(-s % block, ids.dtype)])
    if forced_picks is not None:
        forced_picks = np.asarray(forced_picks)
        forced_picks = np.concatenate([forced_picks, np.zeros(
            forced_picks.shape[:1] + (len(ids) - s,)
            + forced_picks.shape[2:], forced_picks.dtype)], 1)
    picks = []
    attn = _program(_attention_layer, _static(c), mutate, dt, block)
    norm = _program(_norm, eps)
    x = jnp.asarray(params["model.embed_tokens.weight"])[ids] \
        .astype(jnp.float32)
    for i in range(c["num_hidden_layers"]):
        w = layer_weights(params, i)
        x = attn(x, {k: w[k] for k in ATTN_KEYS + ("input_layernorm",)
                     if k in w})
        y = norm(x, w["post_attention_layernorm"])
        if i < c["first_k_dense_replace"]:
            x = x + _program(_swiglu, dt)(y, w["gate_proj"], w["up_proj"],
                                          w["down_proj"])
        else:
            out, idx = experts(
                y, w, c, mutate, None if forced_picks is None
                else forced_picks[len(picks)], experts_held, dt)
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


def compare(got_logits, got_picks, params, c, ids, **kw) -> dict:
    """The two-part comparison of a system's logits and picks on ``ids``
    with the reference: ``{"picks_agree": share, "logits_err": relative
    rms}`` — (a) each routing for itself, (b) the reference forced to the
    system's picks."""
    got_picks = np.asarray(got_picks)
    _, own = forward(params, c, ids, logits_at=[0], **kw)
    agree = float(np.mean([
        len(set(a) & set(b)) / len(a)
        for a, b in zip(got_picks.reshape(-1, got_picks.shape[-1]),
                        own.reshape(-1, own.shape[-1]))]))
    want, _ = forward(params, c, ids, forced_picks=got_picks, **kw)
    got = np.asarray(got_logits, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.sqrt(np.mean((got - want) ** 2))
                / np.sqrt(np.mean(want ** 2)))
    return {"picks_agree": agree, "logits_err": err}


def model_outputs(model, ids):
    """The model's own forward (its dtype, its kernels, one jitted
    ``EvalStep``) on one sequence: ``(logits (s, vocab), picks)``."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import EvalStep
    step = EvalStep(model, lambda m, b: m(b, output_router_picks=True))
    logits, picks = step(paddle.to_tensor(np.asarray(ids)[None]))
    return logits._value[0], np.asarray(picks._value)


def check(model, ctx) -> dict:
    """One seeded ``SEQ``-token sequence through the model's own forward
    and through the reference; returns the checks for ``correct``."""
    c = ctx.config
    n = min(SEQ, c["max_position_embeddings"])
    ids = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 23]).integers(
        0, c["vocab_size"], n, np.int32)
    logits, picks = model_outputs(model, ids)
    params = {k: p._value for k, p in model.named_parameters()}
    r = compare(logits, picks, params, c, ids)
    return {
        f"(a) routed picks, model vs the plain float32 reference, each "
        f"routing for itself on {n} seeded tokens: {r['picks_agree']:.4f} "
        f">= {PICKS_TOLERANCE}": r["picks_agree"] >= PICKS_TOLERANCE,
        f"(b) model logits vs the reference held to the model's picks: "
        f"relative rms error {r['logits_err']:.4f} <= {LOGITS_TOLERANCE}":
        bool(np.isfinite(r["logits_err"])
             and r["logits_err"] <= LOGITS_TOLERANCE)}
