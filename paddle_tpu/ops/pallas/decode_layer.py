"""Fused "decode layer" megakernel: one Pallas launch per decode step
per transformer layer, keeping the (S, d) hidden state in VMEM across
the paged KV read, the gemms, and both RMS-norm folds.

The serving decode block (serving/engine.py) dispatches each layer's
attention, o_proj and MLP as separate XLA ops with an HBM round-trip of
the (S, 1, d) hidden state between every one. RedFuser (PAPERS.md,
arxiv 2603.10026) frames exactly this cascade as the fusion backend
compilers refuse to cross; PR 3 applied it to softmax/layer-norm
chains, this module applies it to the whole decode layer:

- **Marking** (:func:`marking`): the serving engine arms a trace-time
  context while tracing its ONE decode-block program;
  ``models/llama.py`` then wraps each decode layer's cache path (s=1,
  slot-pool positions) in a ``jax.jit``-marked region, so the layer
  appears in the traced jaxpr as ONE ``pjit`` equation named
  ``pt_decode_layer_<mode>`` with a documented positional layout
  (:data:`ARG_LAYOUT`). Marking is dormant outside the fused trace —
  the default decode path traces exactly as before.
- **Recognition + splice** live in ``passes/fusion_decode.py``: the
  pass walks the block jaxpr (recursing into the ``lax.scan`` body),
  validates the marked region really is the attention→o_proj→MLP chain
  (pattern machinery from ``passes/patterns.py``), and replaces it with
  ONE ``closed_call`` traced from :func:`build_fused_callable`.
- **The kernel** (:func:`decode_layer_paged_kernel`): grid
  ``(S, max_blocks)``; per slot the hidden-state row is DMA'd to VMEM
  once, the first grid step folds RMS-norm #1 + the q projection +
  RoPE into VMEM scratch, every step folds one arena block into the
  online softmax (int8 arenas dequantized in registers via the SAME
  ``_deq_block`` as the paged read's scan fallback), and the last step
  runs o_proj, the residual, RMS-norm #2 and the SwiGLU MLP entirely
  out of VMEM — the only HBM traffic per layer is the x row in, the
  out row back, the weights and the quantized KV blocks. The k/v
  projection + arena write happen in a tiny jnp prologue (the arena
  write IS HBM traffic by definition, and k/v are (S, kvh·dh), not the
  hidden state); the kernel recomputes RMS-norm #1 for q instead of
  round-tripping it (FLOPs are free, bandwidth is not — the RedFuser
  trade).
- **Off-TPU fallback**: :func:`build_fused_callable` evaluates the
  captured original region jaxpr — the fallback IS the unfused math,
  so CPU-lane fused streams are bit-identical to unfused ones by
  construction and the quick lane can pin the whole composition
  matrix. The kernel itself is exercised on CPU via interpret mode
  (tests) and dispatched for real only on TPU.

The MLP's gate/up gemms can be chunked over the ff dim
(``ff_chunk``) — the knob the block-size autotuner
(``ops/pallas/autotune.py``) sweeps and persists per device kind.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from . import fused as _fused
from .paged_attention import _deq_block, quantize_kv, _NEG

__all__ = ["marking", "marking_active", "ARG_LAYOUT", "N_CACHE",
           "N_WEIGHTS", "MODES", "build_fused_callable",
           "decode_layer_reference", "kernel_viable"]


def _online_update(q, k, v, j, bs, length, scale, m_ref, l_ref, acc_ref):
    """Fold one fp32 (bs, kvh, d) KV block into the running online
    softmax (max / normalizer / accumulator scratch refs): the
    megakernel's per-table-entry attention step."""
    kvh = k.shape[1]
    h, d = q.shape
    qg = q.reshape(kvh, h // kvh, d)
    s = jnp.einsum("kgd,tkd->kgt", qg, k) * scale   # (kvh, g, bs)
    t = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(t < length, s, _NEG)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.einsum("kgt,tkd->kgd", p, v)
    m_ref[...] = m_new


# ---------------------------------------------------------------------------
# marking: the trace-time handshake between the serving engine and llama
# ---------------------------------------------------------------------------

_MARKING = [0]


def marking_active() -> bool:
    """True while the serving engine is tracing its decode block for
    megakernel fusion (models mark their decode layers only then)."""
    return bool(_MARKING[0])


@contextlib.contextmanager
def marking():
    """Arm decode-layer marking for the duration of one trace."""
    _MARKING[0] += 1
    try:
        yield
    finally:
        _MARKING[0] -= 1


# the marked pjit's positional contract — the fusion pass and the model
# agree on THIS, not on matching 200 primitives through the rope chain.
# aux is the dense per-row pad vector or the paged block table; eps are
# Literal scalars (concrete at trace time, validated by the pass).
ARG_LAYOUT = ("x", "cos", "sin", "eps1", "eps2", "pos", "aux",
              "*cache", "*weights")
WEIGHT_NAMES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")
N_WEIGHTS = len(WEIGHT_NAMES)
N_CACHE = {"dense": 2, "paged": 2, "paged_int8": 4}
MODES = tuple(N_CACHE)
N_FIXED = 7          # x, cos, sin, eps1, eps2, pos, aux


def split_args(mode: str, args):
    """(fixed, cache, weights) views over the flat marked-call args."""
    nc = N_CACHE[mode]
    fixed = args[:N_FIXED]
    cache = args[N_FIXED:N_FIXED + nc]
    wts = args[N_FIXED + nc:]
    return fixed, cache, wts


def _rot_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


# ---------------------------------------------------------------------------
# reference: the unfused math, restated — the kernel-parity oracle
# ---------------------------------------------------------------------------

def decode_layer_reference(mode, x, cos, sin, eps1, eps2, pos, aux,
                           *rest):
    """One decode layer in plain jnp, mirroring the exact math of the
    unfused llama cache path at s=1 (RMSNorm as ``_rms_ref``, per-row
    RoPE, the ``cached_attention`` write/read discipline, SwiGLU MLP).
    THE parity oracle for the Pallas megakernel — production fallback
    instead evaluates the captured original jaxpr (bit-exact by
    construction); tests pin this restatement against that jaxpr too,
    so the oracle can never drift from the model."""
    from . import paged_attention as _pa
    (cache, wts) = split_args(mode, (None,) * N_FIXED + tuple(rest))[1:]
    ln1, wq, wk, wv, wo, ln2, wg, wu, wd = wts
    S, s, d = x.shape
    dh = cos.shape[1]
    h = wq.shape[1] // dh
    kvh = wk.shape[1] // dh
    scale = 1.0 / math.sqrt(dh)
    pos = jnp.asarray(pos, jnp.int32)

    def rms(v, w, eps):
        vf = v.astype(jnp.float32)
        var = jnp.mean(vf * vf, axis=-1, keepdims=True)
        return (vf * jax.lax.rsqrt(var + eps)).astype(v.dtype) * w

    r1 = rms(x, ln1, eps1)
    q = (r1 @ wq).reshape(S, s, h, dh)
    k = (r1 @ wk).reshape(S, s, kvh, dh)
    v = (r1 @ wv).reshape(S, s, kvh, dh)
    pad = aux if mode == "dense" else jnp.zeros((S,), jnp.int32)
    positions = jnp.clip(pos[:, None] + jnp.arange(s)[None, :]
                         - pad[:, None], 0, None)
    c = cos[positions].astype(x.dtype)          # (S, 1, dh)
    sn = sin[positions].astype(x.dtype)

    def rope(t):
        return t * c[:, :, None, :] + _rot_half(t) * sn[:, :, None, :]

    q, k = rope(q), rope(k)
    if mode == "dense":
        ckv, cvv = cache

        def upd(cachev, blockv):
            return jax.vmap(
                lambda cr, xr, p: jax.lax.dynamic_update_slice(
                    cr, xr, (p, 0, 0)))(cachev,
                                        blockv.astype(cachev.dtype), pos)

        ck, cv = upd(ckv, k), upd(cvv, v)
        t_idx = jnp.arange(ck.shape[1])
        qg = q.reshape(S, s, kvh, h // kvh, dh).astype(jnp.float32)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                            ck.astype(jnp.float32)) * scale
        mask = t_idx[None, None, :] <= pos[:, None, None]
        mask = mask & (t_idx[None, None, :] >= pad[:, None, None])
        scores = jnp.where(mask[:, None, None], scores,
                           jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, cv)
        out = out.reshape(S, s, h, dh).astype(q.dtype)
        new_cache = (ck, cv)
    else:
        tbl = aux
        bs = cache[0].shape[1]
        mb = tbl.shape[1]
        tpos = pos[:, None]                       # (S, 1), s == 1
        blk_idx = tpos // bs
        oob = blk_idx >= mb
        blk = jnp.where(oob, 0, jnp.take_along_axis(
            tbl, jnp.clip(blk_idx, 0, mb - 1), axis=1))
        off = jnp.where(oob, 0, tpos % bs)
        if mode == "paged_int8":
            ckv, cvv, skv, svv = cache
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            ck = ckv.at[blk, off].set(kq.astype(ckv.dtype))
            cv = cvv.at[blk, off].set(vq.astype(cvv.dtype))
            sk = skv.at[blk, off].set(ks)
            sv = svv.at[blk, off].set(vs)
            out = _pa.paged_attention_decode_int8(
                q[:, 0], ck, cv, sk, sv, tbl, pos + 1,
                scale=scale)[:, None].astype(q.dtype)
            new_cache = (ck, cv, sk, sv)
        else:
            ckv, cvv = cache
            ck = ckv.at[blk, off].set(k.astype(ckv.dtype))
            cv = cvv.at[blk, off].set(v.astype(cvv.dtype))
            out = _pa.paged_attention_reference(
                q, ck, cv, tbl, pos + 1, scale=scale)
            new_cache = (ck, cv)
    o = out.reshape(S, s, h * dh) @ wo
    h1 = x + o
    r2 = rms(h1, ln2, eps2)
    g1 = r2 @ wg
    act = jax.nn.silu(g1) * (r2 @ wu)
    return (h1 + act @ wd,) + new_cache


# ---------------------------------------------------------------------------
# the Pallas megakernel (paged modes, s == 1)
# ---------------------------------------------------------------------------

# VMEM the resident set may claim (weights + arena block + scratch);
# configs past this fall back to the unfused-math path, loudly visible
# via engine.megakernel_kernel_eligible()
_VMEM_BUDGET = 10 << 20


def _weight_bytes(d, h, kvh, dh, ff):
    return 4 * (d * h * dh          # wq (reshaped (d, h, dh))
                + h * dh * d        # wo
                + 2 * d * ff        # wg, wu
                + ff * d            # wd
                + 2 * d)            # both norm weights


def kernel_viable(mode, x_aval, cache_avals, wt_avals, window=None
                  ) -> bool:
    """Static routing gate for the megakernel: paged modes only, fp32
    hidden state/weights, no sliding window, and the resident set
    (weights + one arena block + scratch) within the VMEM budget.
    Everything else takes the bit-exact fallback."""
    if mode not in ("paged", "paged_int8") or window is not None:
        return False
    if not _fused._pallas_ok():
        return False
    if x_aval.dtype != jnp.float32:
        return False
    if any(w.dtype != jnp.float32 for w in wt_avals):
        return False
    d = x_aval.shape[-1]
    wq, wk = wt_avals[1], wt_avals[2]
    ff = wt_avals[6].shape[1]
    bs, kvh = cache_avals[0].shape[1], cache_avals[0].shape[2]
    dh = cache_avals[0].shape[3]
    if dh % 2 != 0:
        return False                 # rotate-half needs an even head dim
    h = wq.shape[1] // dh
    kv_blk = bs * kvh * dh * (1 if mode == "paged_int8" else 4) * 2
    scratch = 4 * (3 * kvh * (h // kvh) * dh + 2 * d + ff)
    return (_weight_bytes(d, h, kvh, dh, ff) + kv_blk + scratch
            <= _VMEM_BUDGET)


def _tuned_ff_chunk(d: int, ff: int) -> int:
    """MLP ff-dim compute-chunk: the autotuner's knob for this kernel
    (one entry per (d, ff) per device kind). Falls back to the whole ff
    (no chunking) — a tuned chunk must divide ff and stay 128-aligned
    or it is ignored."""
    from .autotune import lookup
    cfg = lookup("decode_layer", {"d": d, "ff": ff})
    if cfg:
        fc = int(cfg.get("ff_chunk", 0))
        if fc > 0 and ff % fc == 0 and fc % 128 == 0:
            return fc
    return ff


def _mega_kernel(tbl_ref, len_ref, x_ref, cos_ref, sin_ref, ln1_ref,
                 wq_ref, wo_ref, ln2_ref, wg_ref, wu_ref, wd_ref,
                 *kv_refs_and_out, bs, scale, nblocks, eps1, eps2,
                 int8, ff_chunk):
    """One grid step = (slot i, table entry j). Scratch (per slot):
    the RoPE'd q and the online-softmax (m, l, acc) — the hidden state
    never leaves VMEM between the attention read, o_proj, the residual
    folds and the MLP."""
    from jax.experimental import pallas as pl

    if int8:
        k_ref, v_ref, sk_ref, sv_ref = kv_refs_and_out[:4]
        o_ref, q_s, m_ref, l_ref, acc_ref = kv_refs_and_out[4:]
    else:
        k_ref, v_ref = kv_refs_and_out[:2]
        o_ref, q_s, m_ref, l_ref, acc_ref = kv_refs_and_out[2:]
    i = pl.program_id(0)
    j = pl.program_id(1)
    kvh, g, dh = acc_ref.shape
    h = kvh * g

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # RMS-norm #1 + q projection + RoPE, straight into VMEM scratch
        xr = x_ref[...].astype(jnp.float32)            # (1, d)
        var = jnp.mean(xr * xr, axis=-1, keepdims=True)
        r1 = xr * jax.lax.rsqrt(var + eps1) * ln1_ref[...]
        q = jnp.einsum("od,dhk->ohk", r1, wq_ref[...])[0]   # (h, dh)
        c = cos_ref[...]                               # (1, dh)
        sn = sin_ref[...]
        q = q * c + _rot_half(q) * sn
        q_s[...] = q.reshape(kvh, g, dh)

    length = len_ref[i]

    @pl.when(j * bs < length)
    def _block():
        if int8:
            k = _deq_block(k_ref[0], sk_ref[0])
            v = _deq_block(v_ref[0], sv_ref[0])
        else:
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
        _online_update(q_s[...].reshape(h, dh), k, v, j, bs, length,
                       scale, m_ref, l_ref, acc_ref)

    @pl.when(j == nblocks - 1)
    def _finalize():
        attn = (acc_ref[...] / l_ref[...]).reshape(1, h * dh)
        o = jnp.dot(attn, wo_ref[...],
                    preferred_element_type=jnp.float32)
        h1 = x_ref[...].astype(jnp.float32) + o        # residual #1
        var = jnp.mean(h1 * h1, axis=-1, keepdims=True)
        r2 = h1 * jax.lax.rsqrt(var + eps2) * ln2_ref[...]
        ff = wg_ref.shape[1]
        if ff_chunk >= ff:
            g1 = jnp.dot(r2, wg_ref[...],
                         preferred_element_type=jnp.float32)
            u = jnp.dot(r2, wu_ref[...],
                        preferred_element_type=jnp.float32)
            act = g1 * jax.nn.sigmoid(g1) * u          # silu(g) * u
            mlp = jnp.dot(act, wd_ref[...],
                          preferred_element_type=jnp.float32)
        else:
            def body(ci, acc):
                sl = pl.ds(ci * ff_chunk, ff_chunk)
                gc = jnp.dot(r2, wg_ref[:, sl],
                             preferred_element_type=jnp.float32)
                uc = jnp.dot(r2, wu_ref[:, sl],
                             preferred_element_type=jnp.float32)
                ac = gc * jax.nn.sigmoid(gc) * uc
                return acc + jnp.dot(ac, wd_ref[sl, :],
                                     preferred_element_type=jnp.float32)
            mlp = jax.lax.fori_loop(0, ff // ff_chunk, body,
                                    jnp.zeros((1, h1.shape[-1]),
                                              jnp.float32))
        o_ref[...] = (h1 + mlp).astype(o_ref.dtype)


def decode_layer_paged_kernel(mode, x, cos, sin, eps1, eps2, pos, tbl,
                              *rest):
    """The megakernel path: jnp prologue (k/v projection + RoPE + arena
    write — mirrors ``cached_attention``'s s=1 discipline, trash-block
    OOB routing included) followed by ONE ``pallas_call`` for
    everything from RMS-norm #1/q through the MLP residual."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (cache, wts) = split_args(mode, (None,) * N_FIXED + tuple(rest))[1:]
    ln1, wq, wk, wv, wo, ln2, wg, wu, wd = wts
    S, s, d = x.shape
    dh = cos.shape[1]
    h = wq.shape[1] // dh
    kvh = wk.shape[1] // dh
    ff = wg.shape[1]
    scale = 1.0 / math.sqrt(dh)
    pos = jnp.asarray(pos, jnp.int32)
    bs = cache[0].shape[1]
    mb = tbl.shape[1]
    int8 = mode == "paged_int8"

    # ---- prologue: k/v projection + RoPE + arena write (jnp) ----------
    xf = x[:, 0].astype(jnp.float32)                   # (S, d)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    r1 = xf * jax.lax.rsqrt(var + eps1) * ln1
    k = (r1 @ wk).reshape(S, kvh, dh)
    v = (r1 @ wv).reshape(S, kvh, dh)
    c = cos[pos].astype(jnp.float32)                   # (S, dh)
    sn = sin[pos].astype(jnp.float32)
    k = k * c[:, None, :] + _rot_half(k) * sn[:, None, :]
    blk_idx = pos // bs
    oob = blk_idx >= mb
    blk = jnp.where(oob, 0, jnp.take_along_axis(
        tbl, jnp.clip(blk_idx, 0, mb - 1)[:, None], axis=1)[:, 0])
    off = jnp.where(oob, 0, pos % bs)
    if int8:
        ckv, cvv, skv, svv = cache
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ck = ckv.at[blk, off].set(kq.astype(ckv.dtype))
        cv = cvv.at[blk, off].set(vq.astype(cvv.dtype))
        sk = skv.at[blk, off].set(ks)
        sv = svv.at[blk, off].set(vs)
        new_cache = (ck, cv, sk, sv)
    else:
        ckv, cvv = cache
        ck = ckv.at[blk, off].set(k.astype(ckv.dtype))
        cv = cvv.at[blk, off].set(v.astype(cvv.dtype))
        new_cache = (ck, cv)

    # ---- the megakernel ----------------------------------------------
    def kv_spec():
        return pl.BlockSpec((1, bs, kvh, dh),
                            lambda i, j, tbl, lens: (tbl[i, j], 0, 0, 0))

    def sc_spec():
        return pl.BlockSpec((1, bs, kvh),
                            lambda i, j, tbl, lens: (tbl[i, j], 0, 0))

    def row(shape):
        return pl.BlockSpec(shape, lambda i, j, tbl, lens: (i,)
                            + (0,) * (len(shape) - 1))

    def whole(arr):
        nd = arr.ndim
        return pl.BlockSpec(arr.shape,
                            lambda i, j, tbl, lens: (0,) * nd)

    wq3 = wq.reshape(d, h, dh)        # weight relayout, not a per-token
    ln1_2 = ln1.reshape(1, d)         # hidden-state round trip
    ln2_2 = ln2.reshape(1, d)
    in_specs = [row((1, d)), row((1, dh)), row((1, dh)),
                whole(ln1_2), whole(wq3), whole(wo), whole(ln2_2),
                whole(wg), whole(wu), whole(wd),
                kv_spec(), kv_spec()]
    operands = [tbl, pos + 1, x[:, 0], c, sn, ln1_2, wq3, wo, ln2_2,
                wg, wu, wd, new_cache[0], new_cache[1]]
    if int8:
        in_specs += [sc_spec(), sc_spec()]
        operands += [new_cache[2], new_cache[3]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, d),
                               lambda i, j, tbl, lens: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, h // kvh, dh), jnp.float32),   # RoPE'd q
            pltpu.VMEM((kvh, h // kvh, 1), jnp.float32),    # m
            pltpu.VMEM((kvh, h // kvh, 1), jnp.float32),    # l
            pltpu.VMEM((kvh, h // kvh, dh), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _mega_kernel, bs=bs, scale=scale, nblocks=mb,
            eps1=float(eps1), eps2=float(eps2), int8=int8,
            ff_chunk=_tuned_ff_chunk(d, ff)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_fused._FORCE_INTERPRET, name="decode_layer",
    )(*operands)
    return (out[:, None, :],) + new_cache


# ---------------------------------------------------------------------------
# the fused callable the pass splices (kernel on TPU, captured-jaxpr
# fallback everywhere else)
# ---------------------------------------------------------------------------

def build_fused_callable(mode, inner_closed, eps1, eps2, *,
                         allow_kernel=True):
    """Build the replacement for one marked decode layer. The returned
    function's __name__ is ``pt_fused_decode_layer`` — the handle the
    no-transient jaxpr walks key on (``call_jaxpr.jaxpr.debug_info``).

    Kernel routing is decided ONCE at trace time from the avals;
    ineligible shapes/modes (and ``allow_kernel=False`` — the
    weight-quant engines, where the in-graph dequant must stay fused
    into the XLA gemm prologue) evaluate the captured original jaxpr,
    which is the unfused math bit-for-bit."""
    from jax.extend.core import jaxpr_as_fun

    invars = inner_closed.jaxpr.invars

    def _use_kernel():
        if not allow_kernel:
            return False
        fixed, cache, wts = split_args(
            mode, tuple(v.aval for v in invars))
        return kernel_viable(mode, fixed[0], cache, wts)

    use_kernel = _use_kernel()

    def pt_fused_decode_layer(*args):
        if use_kernel:
            fixed, cache, wts = split_args(mode, args)
            return decode_layer_paged_kernel(
                mode, fixed[0], fixed[1], fixed[2], eps1, eps2,
                fixed[5], fixed[6], *cache, *wts)
        return tuple(jaxpr_as_fun(inner_closed)(*args))

    pt_fused_decode_layer.uses_kernel = use_kernel
    return pt_fused_decode_layer
