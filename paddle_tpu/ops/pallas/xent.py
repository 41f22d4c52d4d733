"""Fused softmax-cross-entropy: one-pass online logsumexp + label
gather, no ``(N, vocab)`` probability / one-hot intermediates.

Reference parity: the PHI fused softmax_with_cross_entropy CUDA kernel
(paddle/phi/kernels/gpu/cross_entropy_kernel.cu — verify) computes the
per-row loss with a warp-level online softmax; the unfused jaxpr
(one_hot -> mul -> reduce) materializes TWO (N, V) temporaries on top
of the logits. RedFuser (PAPERS.md, arxiv 2603.10026) shows exactly
this cascaded-reduction shape (max -> exp-sum -> gather) is what
accelerator compilers fail to fuse on their own.

TPU-native design: a single Pallas launch per row-block reads the
logits tile once from HBM and produces the per-row ``lse`` and target
log-prob; the backward is a second one-pass kernel writing
``p*ga - onehot*gb`` straight to the cotangent (the only full-width
array it touches IS the returned gradient). Off-TPU the same math runs
as a ``lax.scan`` over vocab chunks — transients stay (N, V/chunks),
so even the fallback jaxpr contains no vocab-sized intermediate, which
tests assert by walking the traced program (see tests/test_passes.py).

Everything is wired behind ``custom_vjp``: fusion passes can splice the
forward into a traced program and gradients still route through the
hand-written backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import fused as _fused

__all__ = ["softmax_xent_rows", "softmax_xent_rows_reference"]

# finite stand-in for -inf inside kernels: keeps padded/garbage rows
# from producing inf-inf=nan while being far below any real logit
_NEG = -1e30


def _tuned_chunk_cap(v: int, default: int = 4096) -> int:
    """The fallback's chunk cap: the autotune table's winner for this
    vocab on this device kind when a valid (stamp-matching) entry
    exists, the documented 4096 otherwise — regression-pinned in
    tests/test_autotune.py."""
    from .autotune import lookup
    cfg = lookup("xent", {"vocab": v})
    if cfg:
        cap = int(cfg.get("chunk_cap", 0))
        if cap > 0:
            return cap
    return default


def _best_chunk(v: int, cap: int = None) -> int:
    """Largest divisor of ``v`` that is <= cap (prefers >= 128).
    ``cap=None`` consults the autotune table (fallback 4096)."""
    if cap is None:
        cap = _tuned_chunk_cap(v)
    for c in range(min(v, cap), 127, -1):
        if v % c == 0:
            return c
    return v


# ---------------------------------------------------------------------------
# chunked-scan fallback (CPU / non-aligned shapes): (N, chunk) transients
# ---------------------------------------------------------------------------

def _rows_scan_fwd(x, labels, chunk_cap=None):
    n, v = x.shape
    c = _best_chunk(v, chunk_cap)
    if c == v:
        xf = x.astype(jnp.float32)
        m = jnp.max(xf, axis=-1)
        s = jnp.sum(jnp.exp(xf - m[:, None]), axis=-1)
        lse = m + jnp.log(s)
        tgt = jnp.take_along_axis(xf, labels[:, None], axis=1)[:, 0]
        return lse - tgt, lse
    nchunks = v // c

    def body(carry, i):
        m, s, tgt = carry
        xc = jax.lax.dynamic_slice_in_dim(x, i * c, c, 1).astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(xc, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(xc - m_new[:, None]), axis=-1)
        in_chunk = (labels >= i * c) & (labels < (i + 1) * c)
        idx = jnp.clip(labels - i * c, 0, c - 1)
        lt = jnp.take_along_axis(xc, idx[:, None], axis=1)[:, 0]
        tgt = jnp.where(in_chunk, lt, tgt)
        return (m_new, s, tgt), None

    init = (jnp.full((n,), _NEG, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, tgt), _ = jax.lax.scan(body, init, jnp.arange(nchunks))
    lse = m + jnp.log(s)
    return lse - tgt, lse


def _rows_scan_bwd(x, labels, lse, ga, gb):
    """dx = softmax * ga[:,None] - onehot * gb[:,None], chunk-wise."""
    n, v = x.shape
    c = _best_chunk(v)
    nchunks = v // c

    def chunk_grad(i):
        xc = jax.lax.dynamic_slice_in_dim(x, i * c, c, 1).astype(jnp.float32)
        p = jnp.exp(xc - lse[:, None])
        idx = jnp.clip(labels - i * c, 0, c - 1)
        in_chunk = (labels >= i * c) & (labels < (i + 1) * c)
        onehot = (jnp.arange(c)[None, :] == idx[:, None]) & in_chunk[:, None]
        return p * ga[:, None] - onehot.astype(jnp.float32) * gb[:, None]

    if nchunks == 1:
        return chunk_grad(0).astype(x.dtype)
    _, dxs = jax.lax.scan(lambda _, i: (None, chunk_grad(i)), None,
                          jnp.arange(nchunks))
    return dxs.transpose(1, 0, 2).reshape(n, v).astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels: one pass over the logits tile per direction
# ---------------------------------------------------------------------------

def _xent_fwd_kernel(x_ref, lab_ref, nll_ref, lse_ref):
    x = x_ref[...].astype(jnp.float32)                 # (R, V)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    m = jnp.max(x, axis=-1, keepdims=True)
    s = jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)
    lse = m + jnp.log(s)
    hit = cols == lab_ref[...]                         # (R, V) vs (R, 1)
    tgt = jnp.max(jnp.where(hit, x, _NEG), axis=-1, keepdims=True)
    nll_ref[...] = lse - tgt
    lse_ref[...] = lse


def _xent_bwd_kernel(x_ref, lab_ref, lse_ref, ga_ref, gb_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    p = jnp.exp(x - lse_ref[...])
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == lab_ref[...]).astype(jnp.float32)
    dx_ref[...] = (p * ga_ref[...]
                   - onehot * gb_ref[...]).astype(dx_ref.dtype)


def _block_rows(v: int) -> int:
    # ~2 MB fp32 tile budget; rows in multiples of the 8-sublane VPU
    budget = (2 << 20) // max(v * 4, 1)
    return max(8, min(256, budget // 8 * 8))


def _pallas_viable(x) -> bool:
    n, v = x.shape
    return _fused._pallas_ok() and v % 128 == 0 and v * 4 * 8 <= (4 << 20)


def _rows_pallas_fwd(x, labels):
    from jax.experimental import pallas as pl

    n, v = x.shape
    br = _block_rows(v)
    grid = (pl.cdiv(n, br),)
    xspec = pl.BlockSpec((br, v), lambda i: (i, 0))
    cspec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    nll, lse = pl.pallas_call(
        _xent_fwd_kernel,
        grid=grid,
        in_specs=[xspec, cspec],
        out_specs=[cspec, cspec],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=_fused._FORCE_INTERPRET, name="fused_xent_fwd",
    )(x, labels[:, None])
    return nll[:, 0], lse[:, 0]


def _rows_pallas_bwd(x, labels, lse, ga, gb):
    from jax.experimental import pallas as pl

    n, v = x.shape
    br = _block_rows(v)
    grid = (pl.cdiv(n, br),)
    xspec = pl.BlockSpec((br, v), lambda i: (i, 0))
    cspec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    dx = pl.pallas_call(
        _xent_bwd_kernel,
        grid=grid,
        in_specs=[xspec, cspec, cspec, cspec, cspec],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((n, v), x.dtype),
        interpret=_fused._FORCE_INTERPRET, name="fused_xent_bwd",
    )(x, labels[:, None], lse[:, None], ga[:, None], gb[:, None])
    return dx


# ---------------------------------------------------------------------------
# custom_vjp entry point
# ---------------------------------------------------------------------------

@jax.custom_vjp
def softmax_xent_rows(x, labels):
    """Per-row softmax cross-entropy core.

    x: (N, V) logits (any float dtype; accumulation is fp32);
    labels: (N,) int32/int64, REQUIRED in-range [0, V) (callers clip —
    ignore_index masking composes outside on the returned rows).
    Returns ``(nll, lse)``: nll[i] = lse[i] - x[i, labels[i]] and the
    per-row logsumexp, both (N,) fp32. Differentiable wrt ``x`` through
    BOTH outputs (d lse/dx = softmax), so label-smoothing algebra on top
    of (nll, lse) has exact gradients.
    """
    out, _ = _rows_fwd(x, labels)
    return out


def _rows_fwd(x, labels):
    labels = labels.astype(jnp.int32)
    if _pallas_viable(x):
        nll, lse = _rows_pallas_fwd(x, labels)
    else:
        nll, lse = _rows_scan_fwd(x, labels)
    return (nll, lse), (x, labels, lse)


def _rows_bwd(res, cts):
    x, labels, lse = res
    g_nll, g_lse = cts
    ga = (g_nll + g_lse).astype(jnp.float32)   # softmax term scale
    gb = g_nll.astype(jnp.float32)             # one-hot term scale
    if _pallas_viable(x):
        dx = _rows_pallas_bwd(x, labels, lse, ga, gb)
    else:
        dx = _rows_scan_bwd(x, labels, lse, ga, gb)
    return dx, None


softmax_xent_rows.defvjp(_rows_fwd, _rows_bwd)


def softmax_xent_rows_reference(x, labels):
    """Unfused parity oracle: full log_softmax + gather (materializes
    (N, V) — tests pin the fused path against this)."""
    logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None],
                              axis=1)[:, 0]
    lse = jnp.max(x.astype(jnp.float32), axis=-1) + jnp.log(
        jnp.sum(jnp.exp(x.astype(jnp.float32)
                        - jnp.max(x.astype(jnp.float32), axis=-1,
                                  keepdims=True)), axis=-1))
    return -tgt, lse
