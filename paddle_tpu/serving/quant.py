"""Weight-only quantized serving: int8/int4 decode weights dequantized
in-gemm inside the ONE compiled decode block.

Plain decode re-reads every parameter byte per step — at serving batch
sizes the step is HBM-bandwidth-bound, so fp32 weights cap decode
tokens/s at (weight bytes)/(HBM GB/s) regardless of MXU headroom. This
module routes the serving weights through the ``nn/quant`` weight-only
machinery: q/k/v/o, MLP gate/up/down and lm_head are quantized ONCE at
engine build (int8 per-channel or per-group absmax; int4 nibble-packed
on the in dim), the backends hold codes + fp32 scales instead of fp32
weights, and the pure decode step dequantizes each weight in-graph
right where it is consumed — XLA fuses the scale multiply into the gemm
prologue, so HBM sees ~4x (int8) / ~8x (int4) fewer weight bytes per
decode step with no separate dequant pass.

Composition (the same contract as spec/tp):

- everything is default-off: pass ``quant=QuantConfig(...)`` (or
  ``quant="int8"``/``"int4"``) to the ``ContinuousBatchingEngine``
  factory, or set ``PT_SERVING_QUANT_WEIGHTS=int8|int4``
  (``PT_SERVING_QUANT_GROUP`` for per-group scales);
- an explicitly passed backend is NEVER rerouted by the env knob, and
  ``quant=`` alongside an explicit backend is refused loudly (the
  quantization is baked into the backend at construction);
- composes with ``kv_int8=`` (int8 KV arena + int8 weights = the
  bandwidth-true stack), ``spec=`` (the verify program dequantizes the
  same codes), and ``tp=`` in mode="exact" (per-shard scales ride the
  SAME PartitionSpecs as their weights: a column-sharded weight's
  per-channel scales split on the out dim). mode="psum" + quant is
  refused (row-sharded int4 packing and group boundaries do not split
  cleanly — a follow-up, not a silent fallback);
- error accounting mirrors the KV arena's EQuARX contract: the
  worst-case elementwise |dequant - fp32| over every quantized weight
  is computed at build time and runtime-queryable via
  ``engine.weight_error_bound()`` /
  ``engine.quant_error_bound()["weights"]``, surfaced as the
  ``pt_serving_weight_error_bound`` gauge next to the KV bound.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from ..nn.quant import dequantize_array, quant_step_bound, quantize_array
from ..observability import metrics as _om
from ..utils.flags import env_int, env_str

__all__ = ["QuantConfig", "resolve_quant_config", "quantize_backend_params",
           "wrap_pure_with_dequant"]

# quant-bound gauges (no-ops until metrics.enable()/PT_METRICS;
# registered at import so the catalog-complete-at-zero contract holds —
# serving/__init__ imports this module eagerly)
_M_KV_BOUND = _om.gauge(
    "pt_serving_kv_error_bound",
    "runtime worst-case |dequant - fp32| over the engine's int8 KV "
    "arena (0 in fp32 mode)")
_M_W_BOUND = _om.gauge(
    "pt_serving_weight_error_bound",
    "build-time worst-case |dequant - fp32| over the engine's "
    "weight-only-quantized decode weights (0 in fp32 mode)")

_BITS = {"int8": 8, "int4": 4}

# the serving weight set: attention + MLP projections and the lm_head —
# the decode step's bandwidth, per the reference quantized_linear scope.
# Embeddings (a gather, not a gemm) and norm/bias vectors stay fp32.
_WEIGHT_PATTERNS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                    "up_proj", "down_proj", "lm_head")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """How to quantize the serving weights. ``weights``: "int8" |
    "int4". ``group_size``: -1 = per-output-channel absmax scales;
    > 0 = one scale per ``group_size`` input rows per channel (must
    divide every quantized weight's in_features — refused loudly
    otherwise, matching the nn/quant contract)."""
    weights: str = "int8"
    group_size: int = -1

    def __post_init__(self):
        if self.weights not in _BITS:
            raise ValueError(
                f"QuantConfig.weights={self.weights!r}; expected 'int8' "
                "or 'int4'")
        if self.group_size != -1 and self.group_size <= 0:
            raise ValueError(
                f"QuantConfig.group_size={self.group_size}; expected -1 "
                "(per-channel) or a positive group size")

    @property
    def bits(self) -> int:
        return _BITS[self.weights]


def resolve_quant_config(quant) -> Optional[QuantConfig]:
    """Normalize the engine's ``quant`` argument: QuantConfig
    pass-through, ``"int8"``/``"int4"`` shorthand, ``True`` -> int8
    defaults, ``False`` -> off, ``None`` -> the
    ``PT_SERVING_QUANT_WEIGHTS`` env knob (empty/unset disables;
    ``PT_SERVING_QUANT_GROUP`` sets the group size)."""
    if isinstance(quant, QuantConfig):
        return quant
    if quant is True:
        return QuantConfig()
    if quant is False:
        return None
    if isinstance(quant, str):
        return QuantConfig(weights=quant)
    if quant is not None:
        raise ValueError(f"quant={quant!r}: pass a QuantConfig, "
                         "'int8'/'int4', True/False, or None "
                         "(env-controlled)")
    w = env_str("PT_SERVING_QUANT_WEIGHTS", "")
    if not w:
        return None
    return QuantConfig(weights=w,
                       group_size=env_int("PT_SERVING_QUANT_GROUP", -1))


@dataclasses.dataclass(frozen=True)
class _QMeta:
    """Per-weight dequant recipe recorded at quantize time (static —
    baked into the compiled program, never traced)."""
    bits: int
    in_features: int
    dtype: object          # original weight dtype the dequant restores


def quantize_backend_params(model, pv, cfg: QuantConfig):
    """Quantize the serving weight set inside a backend's flat ``pv``
    list (aligned with ``model.named_parameters()`` order). Quantized
    entries become ``(codes, scales)`` tuples — a pytree jit/shard_map
    thread through unchanged; everything else keeps its fp32 value.
    Returns ``(new_pv, qmeta: {index: _QMeta}, weight_error_bound)``.

    A model with NO matching 2-D weights is refused loudly: silently
    serving fp32 from a quant= request would be a misconfiguration,
    not a preference (same contract as kv_int8 on an explicit
    backend)."""
    named = list(model.named_parameters())
    if len(named) != len(pv):
        raise ValueError("backend pv is not aligned with "
                         "model.named_parameters() — cannot map weights")
    new_pv = list(pv)
    qmeta: Dict[int, _QMeta] = {}
    bound = 0.0
    for i, (name, _p) in enumerate(named):
        v = pv[i]
        if getattr(v, "ndim", 0) != 2:
            continue
        if not any(pat in name for pat in _WEIGHT_PATTERNS):
            continue
        codes, scales = quantize_array(v, cfg.bits, cfg.group_size)
        new_pv[i] = (codes, scales)
        qmeta[i] = _QMeta(bits=cfg.bits, in_features=int(v.shape[0]),
                          dtype=v.dtype)
        bound = max(bound, quant_step_bound(scales, cfg.bits))
    if not qmeta:
        raise ValueError(
            f"{type(model).__name__} has no quantizable serving weights "
            f"(looked for 2-D parameters matching {_WEIGHT_PATTERNS}) — "
            "weight-only serving quant needs the standard projection "
            "layout")
    return new_pv, qmeta, bound


def dequantize_pv(pv, qmeta: Dict[int, _QMeta]):
    """In-graph inverse of :func:`quantize_backend_params`: rebuild the
    flat fp32 pv the model's forward expects. Runs INSIDE the compiled
    decode/prefill/verify programs — XLA fuses each weight's scale
    multiply into its consumer gemm, so the fp32 weight exists only as
    the gemm operand, never as an HBM round-trip."""
    out = list(pv)
    for i, m in qmeta.items():
        codes, scales = pv[i]
        out[i] = dequantize_array(codes, scales, m.bits,
                                  in_features=m.in_features,
                                  out_dtype=m.dtype)
    return out


def wrap_pure_with_dequant(pure, qmeta: Dict[int, _QMeta]):
    """Wrap a ``build_decode_step`` pure so every program built from it
    (decode block, prefill, chunk, spec verify) dequantizes the
    quantized pv entries at entry — ONE wrapper serves all program
    shapes, which is what keeps quant composable with paged/spec."""
    def pure_q(pv, bv, *args, **kw):
        return pure(dequantize_pv(pv, qmeta), bv, *args, **kw)
    return pure_q


def scale_pspec(weight_spec, scales):
    """PartitionSpec for a quantized weight's scales under
    tensor-parallel serving (mode="exact"): the scales ride the SAME
    axes as their weight's out dim — per-channel ``(out,)`` scales of a
    column-sharded ``P(None, axes)`` weight shard as ``P(axes)``,
    grouped ``(groups, out)`` as ``P(None, axes)``; a replicated weight
    replicates its scales."""
    from jax.sharding import PartitionSpec as P
    dims = tuple(weight_spec)
    if not dims or all(d is None for d in dims):
        return P()
    if len(dims) != 2 or dims[0] is not None:
        raise NotImplementedError(
            f"weight-only quant cannot shard scales for weight spec "
            f"{weight_spec} — only out-dim (column) sharding composes "
            "(tp mode='exact')")
    out_axes = dims[1]
    if scales.ndim == 1:
        return P(out_axes)
    return P(None, out_axes)
