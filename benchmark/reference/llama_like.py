"""The plain reference for the Llama-like configurations (Mistral, Yi).

A straightforward float32 ``jax.numpy`` forward: embedding, then per layer
RMSNorm -> q/k/v -> RoPE (rotate-half) -> grouped-query causal attention ->
output projection -> residual; RMSNorm -> SwiGLU -> residual; final RMSNorm
and the untied head. No kernels, no cache, no batching;
``default_matmul_precision("highest")`` because a float32 matmul on a TPU
runs in lower precision without it. Weights are the model's own values, cast
to float32 one layer at a time so that it fits beside them.

``check`` compares the model's own logits (bf16, its kernels, one jitted
``EvalStep``) with the reference on one seeded sequence during set-up.

Tolerance: rms(model - reference) / rms(reference) <= 0.05. bf16 keeps 8
bits of mantissa, so each rounding is at most 2**-9 relative; about ten
roundings a layer over L layers add up like a random walk to about
2**-9 * sqrt(10 L), 2.5% at 16 layers. Twice that passes bf16 and fails
anything coarser (an 8-bit float rounds at 2**-4) or a dropped term (a
missing RoPE or norm moves the logits by their own size).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 0.05
SEQ = 256


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (s, heads, d); rotate-half with position-major angles."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def layer(x, w, *, heads, kv_heads, eps, theta):
    """One decoder layer on (s, hidden) float32; ``w`` maps short names to
    this layer's weights (Linear weights are stored (in, out))."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s, h = x.shape
    d = h // heads
    y = rms_norm(x, w["input_layernorm"], eps)
    q = rope((y @ w["q_proj"]).reshape(s, heads, d), theta)
    k = rope((y @ w["k_proj"]).reshape(s, kv_heads, d), theta)
    v = (y @ w["v_proj"]).reshape(s, kv_heads, d)
    g = heads // kv_heads                       # query heads per kv head
    q = q.reshape(s, kv_heads, g, d)
    scores = jnp.einsum("skgd,tkd->kgst", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    attn = jnp.einsum("kgst,tkd->skgd", probs, v).reshape(s, h)
    x = x + attn @ w["o_proj"]
    y = rms_norm(x, w["post_attention_layernorm"], eps)
    return x + (jax.nn.silu(y @ w["gate_proj"]) * (y @ w["up_proj"])) \
        @ w["down_proj"]


LAYER_KEYS = ("input_layernorm", "self_attn.q_proj", "self_attn.k_proj",
              "self_attn.v_proj", "self_attn.o_proj",
              "post_attention_layernorm", "mlp.gate_proj", "mlp.up_proj",
              "mlp.down_proj")


def layer_weights(params: dict, i: int) -> dict:
    """Layer ``i``'s weights from either layout: per-layer names
    (``llama.layers.3.mlp.up_proj.weight``) or the stacked trunk
    (``llama.layers.mlp__up_proj__weight`` with a leading layer axis)."""
    out = {}
    for key in LAYER_KEYS:
        name = f"llama.layers.{i}.{key}.weight"
        if name in params:
            out[key.split(".")[-1]] = params[name]
        else:
            stacked = "llama.layers." + key.replace(".", "__") + "__weight"
            out[key.split(".")[-1]] = params[stacked][i]
    return out


def forward(params: dict, c: dict, ids) -> jnp.ndarray:
    """Logits (s, vocab) in float32 for one sequence of token ids."""
    kw = dict(heads=c["num_attention_heads"],
              kv_heads=c["num_key_value_heads"], eps=c["rms_norm_eps"],
              theta=c["rope_theta"])
    one_layer = jax.jit(lambda x, w: layer(x, w, **kw))
    with jax.default_matmul_precision("highest"):
        x = params["llama.embed_tokens.weight"][ids].astype(jnp.float32)
        for i in range(c["num_hidden_layers"]):
            x = one_layer(x, layer_weights(params, i))
        x = rms_norm(x, params["llama.norm.weight"].astype(jnp.float32),
                     c["rms_norm_eps"])
        return jax.jit(lambda a, b: a @ b.astype(jnp.float32))(
            x, params["lm_head.weight"])


def check(model, ctx) -> dict:
    """One seeded ``SEQ``-token sequence through the model's own forward
    and through the reference; returns the check for ``correct``."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import EvalStep
    c = ctx.config
    n = min(SEQ, c["max_position_embeddings"])
    ids = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 23]).integers(
        0, c["vocab_size"], n, np.int32)
    got = EvalStep(model)(paddle.to_tensor(ids[None]))._value[0]
    params = {k: p._value for k, p in model.named_parameters()}
    want = forward(params, c, jnp.asarray(ids))
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.sqrt(np.mean((got - want) ** 2))
                / np.sqrt(np.mean(want ** 2)))
    return {f"model logits vs the plain float32 reference on {n} seeded "
            f"tokens: relative rms error {err:.4f} <= {TOLERANCE}":
            bool(np.isfinite(err) and err <= TOLERANCE)}
