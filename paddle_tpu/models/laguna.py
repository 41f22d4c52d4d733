"""Laguna-class decoder: sliding-window and full grouped-query attention
layers with their own numbers of query heads, YaRN on the full layers, a
per-head sigmoid gate on every attention output, and dropless routed
experts beside one shared expert.

The published block (``model_type: laguna``), pre-norm residual, RMSNorm
(``rms_norm_eps``) before each half, no bias on any projection, SwiGLU.
``layer_types[l]`` says ``full_attention`` or ``sliding_attention``;
``mlp_layer_types[l]`` says ``dense`` (a SwiGLU of ``intermediate_size``:
the ``mlp_only_layers``) or ``sparse`` (routed experts + one shared).

- ``q = y W_q -> (H_l, head_dim)`` with ``H_l =
  num_attention_heads_per_layer[l]``; ``k``, ``v = y W_k``, ``y W_v -> (kvh,
  head_dim)``, ``kvh = num_key_value_heads`` in both kinds.
- RoPE, half-split (``rotate_half``), on the first ``head_dim *
  partial_rotary_factor`` dims of the kind's ``rope_parameters``: in full
  layers ``rope_type: yarn`` (:class:`~.mimo_v2.YaRN`: the ramped
  frequencies and ``attention_factor`` on cos and sin, so on q and on k),
  in sliding layers plain RoPE on all dims; the other dims pass.
- ``score = q_h . k_{h // (H_l / kvh)} / sqrt(head_dim)``, causal; in a
  sliding layer key s is seen from query t iff ``0 <= t - s <
  sliding_window`` (`assumed`: the window counts the token itself, the HF
  convention).
- ``o_h <- sigmoid(y W_g)_h o_h`` (``gating_types: per_head``; `assumed`:
  the gate reads the attention's normed input ``y`` through its own
  ``(hidden, H_l)`` matrix), ``out = concat(o) W_o``. No QK-norm (the
  config has no key for one).
- Experts: ``incubate.distributed.models.moe.route_topk`` with SOFTMAX
  scores over the router's ``num_experts`` (`assumed`: the config names no
  score function; ``norm_topk_prob`` and ``mlp_only_layers`` are the keys
  of the softmax-routed Qwen-MoE line), the top ``num_experts_per_tok``,
  weights renormalised over them and times ``moe_routed_scaling_factor``;
  no selection bias (the layer's zero ``e_score_correction_bias`` moves
  nothing), no softcap; ``y = sum_e w_e SwiGLU_e(y) + SwiGLU_shared(y)``
  (`assumed`: the shared expert has no gate of its own).

The model is ``models.mimo_v2``'s, over this configuration: its layers
are ``MiMoV2DecoderLayer`` (``MiMoV2Attention`` with Laguna's per-layer
arguments, ``attention_sizes``; the dense layer and the shared expert
``MiMoV2MLP``; the routed experts ``DroplessMoE``), which read
``LagunaConfig`` through properties under MiMo's key names; the two groups
of packed cache arenas (``[v 128 | k 128]``, two whole lane tiles a token
a kv head), the counters and ``serving.hybrid.HybridPagedEngine``'s ring
are MiMo's. ``experts_held``
tells the expert layers which global experts they hold (expert
parallelism's share; all by default).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

from .mimo_v2 import FULL, WINDOW, MiMoV2ForCausalLM, YaRN

__all__ = ["LagunaConfig", "LagunaForCausalLM", "laguna_tiny_config"]

_LANES = 128
_FULL_T, _SLIDING_T = "full_attention", "sliding_attention"
_KINDS = {_FULL_T: FULL, _SLIDING_T: WINDOW}


def _published_rope() -> dict:
    return {_FULL_T: {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
        _SLIDING_T: {"rope_type": "default", "rope_theta": 10000,
                     "partial_rotary_factor": 1}}


# the published 48 layers' lists; a config of fewer layers reads the first
_PUBLISHED_LISTS = {
    "layer_types": (_FULL_T, _SLIDING_T, _SLIDING_T, _SLIDING_T) * 12,
    "mlp_layer_types": ("dense",) + ("sparse",) * 47,
    "gating_types": ("per_head",) * 48,
    "num_attention_heads_per_layer": (48, 72, 72, 72) * 12}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256                # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    mlp_only_layers: Sequence[int] = (0,)
    tie_word_embeddings: bool = False
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=_published_rope)
    # per layer (``_PUBLISHED_LISTS`` by default), cut to num_hidden_layers
    layer_types: Optional[Sequence[str]] = None
    mlp_layer_types: Optional[Sequence[str]] = None
    gating_types: Optional[Sequence[str]] = None
    num_attention_heads_per_layer: Optional[Sequence[int]] = None
    moe_apply_router_weight_on_input: bool = False
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0.0
    scoring_func: str = "softmax"         # assumed: no key in the config
    # (first, count): the global routed experts this chip holds
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        for name, default in _PUBLISHED_LISTS.items():
            given = getattr(self, name)
            value = tuple(default if given is None else given)[:n]
            if len(value) != n:
                raise ValueError(f"{name} must give a value for each of the "
                                 f"{n} layers, got {value}")
            setattr(self, name, value)
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if set(self.layer_types) - set(_KINDS) \
                or set(self.mlp_layer_types) - {"dense", "sparse"} \
                or set(self.gating_types) - {"per_head"}:
            raise ValueError(
                "layer_types, mlp_layer_types and gating_types take "
                f"{sorted(_KINDS)}, dense / sparse and per_head")
        if any((t == "dense") != (i in self.mlp_only_layers)
               for i, t in enumerate(self.mlp_layer_types)):
            raise ValueError("mlp_layer_types and mlp_only_layers disagree")
        if self.tie_word_embeddings or self.attention_bias:
            raise ValueError("the Laguna head is untied and its projections "
                             "have no bias")
        if self.moe_apply_router_weight_on_input \
                or self.moe_router_logit_softcapping:
            raise ValueError("router weights on the expert inputs and a "
                             "softcapped router are not built")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("every layer's query heads must be whole groups "
                             "of the kv heads")

    # -- what models.mimo_v2 reads of a configuration -------------------------
    @property
    def hybrid_layer_pattern(self) -> Tuple[int, ...]:
        return tuple(_KINDS[t] for t in self.layer_types)

    @property
    def moe_layer_freq(self) -> Tuple[int, ...]:
        return tuple(int(t == "sparse") for t in self.mlp_layer_types)

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor

    n_group = topk_group = 1            # one group of experts: no group rule

    @property
    def v_head_dim(self) -> int:
        return self.head_dim

    @property
    def layernorm_epsilon(self) -> float:
        return self.rms_norm_eps

    @property
    def kv_row(self) -> int:
        """Values an arena stores a token a kv head: ``[v | k]``, whole
        lane tiles (256 at head_dim 128: nothing padded)."""
        return -(-2 * self.head_dim // _LANES) * _LANES

    def kv_heads(self, kind: int) -> int:
        return self.num_key_value_heads

    def attention_sizes(self, layer_idx: int) -> dict:
        """``MiMoV2Attention``'s arguments for layer ``layer_idx``."""
        rp = self.rope_parameters[self.layer_types[layer_idx]]
        yarn = None
        if rp.get("rope_type", "default") == "yarn":
            yarn = YaRN(float(rp["factor"]),
                        int(rp["original_max_position_embeddings"]),
                        float(rp["beta_fast"]), float(rp["beta_slow"]),
                        float(rp.get("attention_factor",
                                     0.1 * math.log(rp["factor"]) + 1.0)))
        elif rp.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {rp['rope_type']!r} is not built")
        rot = int(self.head_dim * rp.get("partial_rotary_factor", 1.0))
        return dict(heads=self.num_attention_heads_per_layer[layer_idx],
                    theta=float(rp["rope_theta"]), sink=False,
                    rotary_dim=rot // 2 * 2, yarn=yarn,
                    gate=self.gating_types[layer_idx] == "per_head",
                    value_scale=1.0)


def laguna_tiny_config(**kw):
    """Every form of layer in five (dense + full, experts + sliding x 3,
    experts + full), heads 12 / 18 over 2 kv heads, a window of 16, and
    YaRN with 32 original positions, so that a test at 96 positions is
    past them."""
    rope = _published_rope()
    rope["full_attention"].update(factor=4, original_max_position_embeddings=32,
                                  attention_factor=0.1 * math.log(4) + 1.0)
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, shared_expert_intermediate_size=32,
                num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
                sliding_window=16, num_experts=32, num_experts_per_tok=10,
                num_attention_heads_per_layer=(12, 18, 18, 18, 12),
                rope_parameters=rope)
    base.update(kw)
    return LagunaConfig(**base)


class LagunaForCausalLM(MiMoV2ForCausalLM):
    """``MiMoV2ForCausalLM`` over a :class:`LagunaConfig`: the same layers
    (each with its shared expert), the same two groups of packed arenas
    (``kv_cache_groups``: the window, a full layer's leaf ``(blocks,
    block_size * kvh, 256)`` and a sliding layer's ``(window_blocks,
    block_size * kvh, 256)``, the counters' leaf last), the same expert
    counters, the same forward with and without a cache."""
