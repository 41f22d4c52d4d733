"""ERNIE-style Mixture-of-Experts LM — a capacity-routed TOY, the EP
(expert-parallel) training baseline. Not a servable block.

What it is: a LayerNorm / GELU / learned-positions decoder where every
`moe_every`-th layer's FFN is a GShard top-2-of-8 ``MoELayer`` that DROPS
tokens past ``capacity_factor`` (1.25) and whose experts are biased GELU
MLPs; training only, no cache path. It exercises the dispatch/combine
einsums under an "ep" mesh axis and nothing a deployed sparse model needs.
The dropless top-k SwiGLU expert layer (told which experts it holds, exact
for any routing, inside the serving engine's two programs) is
``incubate.distributed.models.moe.DroplessMoE``; ``models/deepseek_v3.py``
is the servable model built on it (ROADMAP D5).

Reference parity: ERNIE-MoE trained through
paddle.incubate.distributed.models.moe.MoELayer with the expert comm group
from HybridCommunicateGroup (reference: python/paddle/incubate/distributed/
models/moe/moe_layer.py — verify); the model itself lives in the ERNIE
ecosystem repo, SURVEY §1 requires an in-repo equivalent.

TPU-native design: the stacked expert weights carry a partition spec over
the "ep" mesh axis — the dispatch/combine einsums lower to exactly the
all-to-all the reference's global_scatter / global_gather ops implement by
hand (SURVEY §2.3 EP row)."""
from __future__ import annotations

from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..incubate.distributed.models.moe import MoELayer
from ..ops.creation import arange
from ..ops.manipulation import reshape

__all__ = ["ErnieMoEConfig", "ErnieMoEModel", "ErnieMoEForCausalLM",
           "ernie_moe_tiny_config", "ernie_moe_base_config"]


@dataclass
class ErnieMoEConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2              # every 2nd layer is MoE (GShard style)
    gate: str = "gshard"
    aux_loss_weight: float = 0.01
    expert_parallel: bool = True    # partition experts over "ep"
    tensor_parallel: bool = False
    dropout: float = 0.0
    dtype: str = "float32"


def ernie_moe_tiny_config(**kw):
    base = dict(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=256,
                max_position_embeddings=128, num_experts=4)
    base.update(kw)
    return ErnieMoEConfig(**base)


def ernie_moe_base_config(**kw):
    return ErnieMoEConfig(**kw)


# Attention is identical to GPT's (duck-typed on hidden_size /
# num_attention_heads / dropout / tensor_parallel config fields).
from .gpt import GPTAttention as ErnieMoEAttention  # noqa: E402


class ErnieMoEBlock(nn.Layer):
    def __init__(self, config: ErnieMoEConfig, use_moe: bool):
        super().__init__()
        h = config.hidden_size
        self.ln_1 = nn.LayerNorm(h, epsilon=config.layer_norm_epsilon)
        self.attn = ErnieMoEAttention(config)
        self.ln_2 = nn.LayerNorm(h, epsilon=config.layer_norm_epsilon)
        self.use_moe = use_moe
        if use_moe:
            self.mlp = MoELayer(
                d_model=h, num_expert=config.num_experts,
                d_hidden=config.intermediate_size, top_k=config.top_k,
                capacity_factor=config.capacity_factor, gate=config.gate,
                expert_axis="ep" if config.expert_parallel else None)
        else:
            self.mlp = nn.Sequential(
                nn.Linear(h, config.intermediate_size), nn.GELU(),
                nn.Linear(config.intermediate_size, h))

    def forward(self, x, attn_mask=None):
        x = x + self.attn(self.ln_1(x), attn_mask)
        return x + self.mlp(self.ln_2(x))


class ErnieMoEModel(nn.Layer):
    def __init__(self, config: ErnieMoEConfig):
        super().__init__()
        self.config = config
        # N(0, 0.02) embedding init (see gpt.py: wider init + tied head
        # degenerates the logits at init)
        from ..param_attr import ParamAttr
        from ..nn import initializer as I
        emb_attr = lambda: ParamAttr(initializer=I.Normal(0.0, 0.02))
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=emb_attr())
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size, weight_attr=emb_attr())
        self.layers = nn.LayerList([
            ErnieMoEBlock(config,
                          use_moe=(i % config.moe_every ==
                                   config.moe_every - 1))
            for i in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, attn_mask=None):
        b, s = input_ids.shape
        pos = arange(0, s, dtype="int64")
        x = self.wte(input_ids) + self.wpe(pos)
        for block in self.layers:
            x = block(x, attn_mask)
        return self.ln_f(x)

    def aux_loss(self):
        """Sum of gate load-balance losses from the last forward."""
        total = None
        for layer in self.layers:
            if layer.use_moe and layer.mlp.l_aux is not None:
                total = layer.mlp.l_aux if total is None \
                    else total + layer.mlp.l_aux
        return total


class ErnieMoEForCausalLM(nn.Layer):
    def __init__(self, config: ErnieMoEConfig):
        super().__init__()
        self.config = config
        self.ernie = ErnieMoEModel(config)

    def forward(self, input_ids, labels=None, attn_mask=None):
        from ..ops.math import matmul
        h = self.ernie(input_ids, attn_mask)
        logits = matmul(h, self.ernie.wte.weight, transpose_y=True)
        if labels is None:
            return logits
        loss = F.cross_entropy(logits, labels, reduction="mean")
        aux = self.ernie.aux_loss()
        if aux is not None:
            loss = loss + self.config.aux_loss_weight * aux
        return loss, logits
