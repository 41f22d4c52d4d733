"""Fused Pallas TPU kernels for the transformer hot path.

Reference parity: the reference ships fused CUDA kernels for exactly
these ops — fused_rms_norm / rms_norm_kernel, fused_rope,
adamw multi-tensor kernel (paddle/phi/kernels/fusion/gpu/,
paddle/phi/kernels/gpu/adamw_kernel.cu — verify).

TPU-native design: each kernel is one pass HBM->VMEM->HBM tiled to the
VPU (8x128 lanes): RMSNorm fuses residual-add + normalize + scale;
RoPE rotates q and k in one launch; AdamW updates param + both moments
in a single read-modify-write per block (the win over XLA's default is
fewer HBM round-trips when the optimizer update is not fused into the
step program). Every entry point has an identical-math jnp fallback
(used off-TPU and as the custom-vjp backward), so numerics are testable
on CPU via interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


# tests set this to run the Pallas kernels in interpret mode on CPU so
# the kernel code itself is exercised without TPU hardware
_FORCE_INTERPRET = False


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_gate(force_interpret: bool = False) -> bool:
    """THE dispatch gate of every Pallas kernel in this package: on a TPU
    (or in a test's forced interpret mode), and not in a program GSPMD
    will partition.

    jax refuses to lower a Mosaic kernel into a multi-device program
    outside a fully-manual ``shard_map`` — "NotImplementedError: Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in
    a shard_map." (compile-only rehearsal of the dp=2 x mp=2 train step
    for a described v5e:2x2, PR 21). The kernels do not carry their own
    shard_map yet, so the gate closes while the process-current mesh
    (``distributed.mesh``) spans several devices and the trace is not
    inside a fully-manual shard_map: under the dp x mp x pp trainer every
    fused kernel takes its identical-math jnp twin and ``sdpa`` its XLA
    route (``sdpa_last_dispatch()`` says so). Tensor-parallel serving
    traces inside shard_map and keeps the kernels."""
    if not (force_interpret or _on_tpu()):
        return False
    from ...distributed.mesh import get_current_mesh
    mesh = get_current_mesh()
    return mesh is None or mesh.size == 1 \
        or jax.sharding.get_abstract_mesh().are_all_axes_manual


def _pallas_ok() -> bool:
    return pallas_gate(_FORCE_INTERPRET)


def _round_up(n, m):
    return (n + m - 1) // m * m


# Mosaic double-buffers every pipelined block inside a 16 MiB scoped VMEM
# limit (v5e) and the kernel bodies add a few block-sized temporaries, so
# the row kernels keep their pipelined buffers to a quarter of it.
_VMEM_BLOCK_BUDGET = 4 * 1024 * 1024


def _block_rows(rows: int, row_bytes: int, n_blocked: int) -> int:
    """Rows per grid step for a kernel that pipelines ``n_blocked``
    row-blocked operands (inputs + outputs) of ``row_bytes`` padded VMEM
    bytes per row: the largest multiple of 8 (at most 256) whose double
    buffers fit ``_VMEM_BLOCK_BUDGET`` — sized from the width and dtype,
    not a constant, so hidden 4096 / 32x128 heads compile where a fixed
    256 rows asked the v5e for 16-22 MiB."""
    fit = _VMEM_BLOCK_BUDGET // (2 * n_blocked * row_bytes)
    return min(_round_up(rows, 8), max(8, min(256, fit // 8 * 8)))


# ---------------------------------------------------------------------------
# fused RMSNorm (+ residual)
# ---------------------------------------------------------------------------

def _rms_ref(x, weight, eps, residual):
    if residual is not None:
        x = x + residual
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight
    return (out, x) if residual is not None else out


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype) \
        * w_ref[...]


def _rms_res_kernel(x_ref, r_ref, w_ref, o_ref, s_ref, *, eps):
    s = x_ref[...] + r_ref[...]
    s_ref[...] = s
    x = s.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype) \
        * w_ref[...]


def _rms_pallas(x, weight, eps, residual):
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    h = orig_shape[-1]
    rows = x.size // h
    x2 = x.reshape(rows, h)
    block_rows = _block_rows(rows, h * x.dtype.itemsize,
                             2 if residual is None else 4)
    grid = (pl.cdiv(rows, block_rows),)
    row_spec = pl.BlockSpec((block_rows, h), lambda i: (i, 0))
    w_spec = pl.BlockSpec((h,), lambda i: (0,))
    if residual is None:
        out = pl.pallas_call(
            functools.partial(_rms_kernel, eps=eps),
            grid=grid,
            in_specs=[row_spec, w_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((rows, h), x.dtype),
            interpret=_FORCE_INTERPRET, name="fused_rms_norm",
        )(x2, weight)
        return out.reshape(orig_shape)
    r2 = residual.reshape(rows, h)
    out, s = pl.pallas_call(
        functools.partial(_rms_res_kernel, eps=eps),
        grid=grid,
        in_specs=[row_spec, row_spec, w_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, h), x.dtype),
                   jax.ShapeDtypeStruct((rows, h), x.dtype)],
        interpret=_FORCE_INTERPRET, name="fused_rms_norm_residual",
    )(x2, r2, weight)
    return out.reshape(orig_shape), s.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_rms_norm_core(x, weight, eps):
    if _pallas_ok():
        return _rms_pallas(x, weight, eps, None)
    return _rms_ref(x, weight, eps, None)


def _rms_fwd(x, weight, eps):
    return _fused_rms_norm_core(x, weight, eps), (x, weight)


def _rms_bwd(eps, saved, ct):
    x, weight = saved
    _, vjp = jax.vjp(lambda a, w: _rms_ref(a, w, eps, None), x, weight)
    return vjp(ct)


_fused_rms_norm_core.defvjp(_rms_fwd, _rms_bwd)


def fused_rms_norm(x, weight, eps: float = 1e-6,
                   residual: Optional[jax.Array] = None):
    """RMSNorm, optionally fused with a residual add.

    Without residual: returns normalized(x) * weight.
    With residual: returns (normalized(x + residual) * weight,
    x + residual) — the second output feeds the next skip connection
    (the reference's fused_rms_norm contract).
    """
    if residual is None:
        return _fused_rms_norm_core(x, weight, eps)
    # residual path: differentiable via the reference impl (two outputs);
    # pallas forward when available
    if _pallas_ok():
        @jax.custom_vjp
        def core(x_, r_, w_):
            return _rms_pallas(x_, w_, eps, r_)

        def fwd(x_, r_, w_):
            return core(x_, r_, w_), (x_, r_, w_)

        def bwd(saved, cts):
            x_, r_, w_ = saved
            _, vjp = jax.vjp(
                lambda a, r, w: _rms_ref(a, w, eps, r), x_, r_, w_)
            return vjp(cts)

        core.defvjp(fwd, bwd)
        return core(x, residual, weight)
    return _rms_ref(x, weight, eps, residual)


def layer_norm_one_pass(x, eps: float, axes=(-1,)):
    """Normalize over ``axes`` with fp32 accumulation, reading x ONCE:
    shifted one-pass moments var = E[(x-s)^2] - E[x-s]^2 with s the
    per-row first element. The shift kills the catastrophic
    cancellation the textbook E[x^2]-E[x]^2 form hits when |mean| >>
    std (measured: 8.8e2 max err at offset 1e4 unshifted vs 5.6e-4
    shifted); the output is shift-invariant so stop_gradient(s) is
    exact. Shared by nn.functional.layer_norm and the fusion pass's
    layer_norm rewrite — fix numerics HERE, once."""
    axes = tuple(a % x.ndim for a in axes)
    xf = x.astype(jnp.float32)
    idx = tuple(slice(0, 1) if a in axes else slice(None)
                for a in range(x.ndim))
    shift = jax.lax.stop_gradient(xf[idx])
    d = xf - shift
    dm = jnp.mean(d, axis=axes, keepdims=True)
    d2 = jnp.mean(d * d, axis=axes, keepdims=True)
    var = jnp.maximum(d2 - dm * dm, 0.0)
    return ((d - dm) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


# ---------------------------------------------------------------------------
# fused rotary position embedding
# ---------------------------------------------------------------------------

def _rope_ref(q, k, cos, sin):
    """(b, s, h, d) with cos/sin (s, d) — rotate-half convention."""
    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], axis=-1)

    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return q * c + rot(q) * s, k * c + rot(k) * s


def _rope_kernel(q_ref, k_ref, c_ref, s_ref, oq_ref, ok_ref):
    c = c_ref[...]                   # (rows, 1, d): broadcasts over heads
    s = s_ref[...]

    def rot(x):
        half = x.shape[-1] // 2
        x1 = x[..., :half]
        x2 = x[..., half:]
        return jnp.concatenate([-x2, x1], axis=-1)

    q = q_ref[...]                   # (rows, h, d)
    k = k_ref[...]
    oq_ref[...] = q * c + rot(q) * s
    ok_ref[...] = k * c + rot(k) * s


def _rope_pallas(q, k, cos, sin):
    from jax.experimental import pallas as pl

    b, sq, h, d = q.shape
    # flatten to (b*s, h, d): Pallas TPU requires the last TWO block dims
    # aligned (8, 128) or equal to the array dims — (h, d) are kept whole,
    # the row dim is the grid. cos/sin are pre-broadcast over the batch so
    # each row block reads matching angles.
    rows = b * sq
    q3 = q.reshape(rows, h, d)
    k3 = k.reshape(rows, h, d)
    # (rows, 1, d): already rank-3 so the kernel never reshapes (Mosaic
    # cannot shape-cast vectors), middle dim broadcasts over heads
    c2 = jnp.broadcast_to(cos[None], (b, sq, d)).reshape(rows, 1, d)
    s2 = jnp.broadcast_to(sin[None], (b, sq, d)).reshape(rows, 1, d)
    # q, k and both outputs ride the pipeline as (rb, h, d) blocks; in
    # VMEM the trailing (h, d) tile pads to the dtype's (sublane, 128)
    sub = 32 // q.dtype.itemsize
    row_bytes = _round_up(h, sub) * _round_up(d, 128) * q.dtype.itemsize
    rb = min(rows, _block_rows(rows, row_bytes, 4))
    grid = (pl.cdiv(rows, rb),)
    qspec = pl.BlockSpec((rb, h, d), lambda i: (i, 0, 0))
    cspec = pl.BlockSpec((rb, 1, d), lambda i: (i, 0, 0))
    oq, ok = pl.pallas_call(
        _rope_kernel,
        grid=grid,
        in_specs=[qspec, qspec, cspec, cspec],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((rows, h, d), q.dtype),
                   jax.ShapeDtypeStruct((rows, h, d), k.dtype)],
        interpret=_FORCE_INTERPRET, name="fused_rope",
    )(q3, k3, c2, s2)
    return oq.reshape(q.shape), ok.reshape(k.shape)


@jax.custom_vjp
def fused_rope(q, k, cos, sin):
    """Apply rotary embeddings to q and k in one fused launch.
    q, k: (b, s, h, d); cos, sin: (s, d). GQA (fewer kv heads) runs as
    two launches — rope is per-head, so the kernel is reused per
    tensor."""
    if _pallas_ok():
        if q.shape == k.shape:
            return _rope_pallas(q, k, cos, sin)
        oq, _ = _rope_pallas(q, q, cos, sin)
        ok, _ = _rope_pallas(k, k, cos, sin)
        return oq, ok
    return _rope_ref(q, k, cos, sin)


def _rope_fwd(q, k, cos, sin):
    return fused_rope(q, k, cos, sin), (cos, sin)


def _rope_bwd(saved, cts):
    cos, sin = saved
    ctq, ctk = cts

    # rotation is orthogonal: the vjp is rotation by -theta
    def unrot(ct):
        def rot_inv(x):
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([x2, -x1], axis=-1)
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        return ct * c + rot_inv(ct) * s

    return unrot(ctq), unrot(ctk), None, None


fused_rope.defvjp(_rope_fwd, _rope_bwd)


# ---------------------------------------------------------------------------
# fused AdamW update
# ---------------------------------------------------------------------------

def _adamw_ref(p, g, m, v, lr, beta1, beta2, eps, weight_decay, step):
    m_new = beta1 * m + (1 - beta1) * g
    v_new = beta2 * v + (1 - beta2) * g * g
    mhat = m_new / (1 - beta1 ** step)
    vhat = v_new / (1 - beta2 ** step)
    p_new = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p)
    return p_new, m_new, v_new


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  po_ref, mo_ref, vo_ref):
    lr = sc_ref[0]
    beta1 = sc_ref[1]
    beta2 = sc_ref[2]
    eps = sc_ref[3]
    wd = sc_ref[4]
    bc1 = sc_ref[5]     # 1 - beta1**step
    bc2 = sc_ref[6]     # 1 - beta2**step
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m_new = beta1 * m + (1 - beta1) * g
    v_new = beta2 * v + (1 - beta2) * g * g
    mhat = m_new / bc1
    vhat = v_new / bc2
    p_new = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    vo_ref[...] = v_new.astype(vo_ref.dtype)


def fused_adamw(p, g, m, v, lr, beta1=0.9, beta2=0.999, eps=1e-8,
                weight_decay=0.01, step=1):
    """One-pass AdamW: reads p/g/m/v once, writes p/m/v once, every
    tensor in its OWN dtype (fp32 master moments, or bf16 moments beside
    bf16 params); the update itself runs in fp32 registers."""
    if not _pallas_ok() or p.size < 1024:
        return _adamw_ref(p, g, m, v, lr, beta1, beta2, eps,
                          weight_decay, step)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = p.size
    lanes = 128
    rows = pl.cdiv(n, lanes)
    pad = rows * lanes - n

    def flat(x):
        x = x.reshape(-1)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows, lanes)

    scalars = jnp.asarray(
        [lr, beta1, beta2, eps, weight_decay,
         1 - beta1 ** step, 1 - beta2 ** step], jnp.float32)
    block_rows = min(512, _round_up(rows, 8))
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    po, mo, vo = pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec, sspec],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), p.dtype),
            jax.ShapeDtypeStruct((rows, lanes), m.dtype),
            jax.ShapeDtypeStruct((rows, lanes), v.dtype),
        ],
        interpret=_FORCE_INTERPRET, name="fused_adamw",
    )(flat(p), flat(g), flat(m), flat(v), scalars)

    def unflat(x, shape):
        return x.reshape(-1)[:n].reshape(shape)

    return unflat(po, p.shape), unflat(mo, m.shape), unflat(vo, v.shape)
