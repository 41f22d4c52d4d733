"""Mixture-of-Experts with expert parallelism.

Reference parity: python/paddle/incubate/distributed/models/moe/
(MoELayer, GShardGate top-2, SwitchGate top-1, NaiveGate,
global_scatter/global_gather alltoall ops — verify).

TPU-native design: GShard-style *dense dispatch* — top-k gating builds a
(tokens → expert, capacity) one-hot dispatch tensor and the routed matmuls
are einsums that XLA maps onto the MXU. Expert weights carry a partition
spec over the expert mesh axis; under jit GSPMD turns the dispatch einsum
into exactly the all-to-all the reference's global_scatter implements by
hand. Capacity + GShard aux load-balance loss included.

:class:`DroplessMoE` is the other kind of block: no capacity and no dropped
token (sort the picks by expert, grouped matmuls over the experts HELD,
weighted combine), SwiGLU experts, a sigmoid/softmax router with the
``noaux_tc`` selection bias. It is told which experts it holds, routes over
the published router width and computes its own experts' part — what expert
parallelism asks of a layer, and what one chip of a cut deployment runs
without the exchange. ``models/deepseek_v3.py`` is built on it."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....nn import functional as F
from ....nn.common import Linear
from ....nn.layer import Layer
from ....tensor import Tensor, apply_op

__all__ = ["MoELayer", "NaiveGate", "GShardGate", "SwitchGate", "ExpertMLP",
           "DroplessMoE", "route_topk", "dropless_expert_mix"]


class BaseGate(Layer):
    def __init__(self, d_model, num_expert):
        super().__init__()
        self.d_model = d_model
        self.num_expert = num_expert


class NaiveGate(BaseGate):
    """top-k gate, no aux loss."""

    def __init__(self, d_model, num_expert, topk=2):
        super().__init__(d_model, num_expert)
        self.gate = Linear(d_model, num_expert, bias_attr=False)
        self.topk = topk

    def forward(self, x):
        return self.gate(x), None


class GShardGate(BaseGate):
    """top-2 gate with GShard load-balance aux loss (reference:
    moe/gate/gshard_gate.py — verify)."""

    def __init__(self, d_model, num_expert, topk=2, capacity_factor=1.25,
                 group=None):
        super().__init__(d_model, num_expert)
        self.gate = Linear(d_model, num_expert, bias_attr=False)
        self.topk = topk
        self.capacity_factor = capacity_factor

    def forward(self, x):
        logits = self.gate(x)

        def aux(lg):
            probs = jax.nn.softmax(lg, axis=-1)      # (tokens, E)
            top1 = jnp.argmax(lg, axis=-1)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(
                jax.nn.one_hot(top1, lg.shape[-1], dtype=lg.dtype), axis=0)
            return jnp.sum(me * ce) * lg.shape[-1]
        loss = apply_op(aux, logits)
        return logits, loss


class SwitchGate(BaseGate):
    """top-1 switch gate with load-balance loss (reference:
    moe/gate/switch_gate.py — verify)."""

    def __init__(self, d_model, num_expert, topk=1, capacity_factor=1.25,
                 group=None):
        super().__init__(d_model, num_expert)
        self.gate = Linear(d_model, num_expert, bias_attr=False)
        self.topk = 1
        self.capacity_factor = capacity_factor

    forward = GShardGate.forward


class ExpertMLP(Layer):
    """Stacked expert FFN weights: (E, d, ffn) + (E, ffn, d) einsums."""

    def __init__(self, num_expert, d_model, d_hidden, activation=F.gelu):
        super().__init__()
        self.w1 = self.create_parameter((num_expert, d_model, d_hidden))
        self.b1 = self.create_parameter((num_expert, 1, d_hidden),
                                        is_bias=True)
        self.w2 = self.create_parameter((num_expert, d_hidden, d_model))
        self.b2 = self.create_parameter((num_expert, 1, d_model),
                                        is_bias=True)
        self.activation = activation

    def set_expert_axis(self, axis_name):
        for p in (self.w1, self.b1, self.w2, self.b2):
            spec = [None] * p._value.ndim
            spec[0] = axis_name
            p._sharding_spec = P(*spec)
            p.is_distributed = True

    def forward(self, x):
        """x: (E, capacity, d) → (E, capacity, d)."""
        from ....ops.math import einsum
        h = einsum("ecd,edh->ech", x, self.w1) + self.b1
        h = self.activation(h)
        return einsum("ech,ehd->ecd", h, self.w2) + self.b2


class MoELayer(Layer):
    """reference: moe_layer.py MoELayer(gate, experts, ...) — verify.

    forward(x: (b, s, d)) -> (b, s, d); aux loss on self.l_aux.

    TPU-native dispatch (r4, VERDICT r3 #4): sort-based capacity routing
    builds DUAL index maps (token→slot and slot→token sentinel-padded,
    ops/pallas/moe_dispatch.build_index_maps); dispatch, combine, and
    both their custom-vjp backwards are then pure row-GATHERS — no
    scatter HLO anywhere in the compiled step (scatters serialize on
    TPU). `dispatch_mode="scatter"` keeps the r3 buf.at[slot].set path
    as the parity reference; PT_MOE_GATHER=pallas routes the gathers
    through the Pallas scalar-prefetch row kernel. Memory is
    O(T·d + E·cap·d) — no dense (E, cap, T) one-hots. Under jit with
    expert weights sharded over the "ep" mesh axis GSPMD partitions the
    expert batch over experts and inserts the token all-to-all the
    reference's global_scatter/global_gather implement by hand."""

    def __init__(self, d_model, experts=None, gate=None, num_expert=None,
                 d_hidden=None, top_k=2, capacity_factor=1.25,
                 expert_axis=None, recompute_interval=0, group=None,
                 dispatch_mode=None):
        super().__init__()
        # "gather" (default): dispatch/combine AND both their vjps are
        # row-gathers over the dual slot<->token index maps — no scatter
        # HLO anywhere (scatters serialize on TPU). "scatter" keeps the
        # r3 buf.at[slot].set path as the parity reference.
        # PT_MOE_GATHER=pallas additionally routes the gathers through
        # the Pallas scalar-prefetch kernel (ops/pallas/moe_dispatch).
        from ....utils.flags import env_str
        self.dispatch_mode = (dispatch_mode
                              or env_str("PT_MOE_DISPATCH", "gather"))
        if gate is None:
            gate = GShardGate(d_model, num_expert, topk=top_k,
                              capacity_factor=capacity_factor)
        if isinstance(gate, str):
            gate = {"gshard": GShardGate, "switch": SwitchGate,
                    "naive": NaiveGate}[gate](d_model, num_expert,
                                              topk=top_k)
        self.gate = gate
        if experts is None:
            experts = ExpertMLP(num_expert, d_model, d_hidden)
        self.experts = experts
        self.num_expert = num_expert or getattr(gate, "num_expert")
        self.top_k = getattr(gate, "topk", top_k)
        # gate-level capacity_factor wins (reference keeps it on the gate)
        self.capacity_factor = getattr(gate, "capacity_factor",
                                       capacity_factor) or capacity_factor
        self.l_aux = None
        if expert_axis is not None and hasattr(experts, "set_expert_axis"):
            experts.set_expert_axis(expert_axis)

    def _capacity(self, tokens: int) -> int:
        cap = int(math.ceil(self.capacity_factor * tokens * self.top_k
                            / self.num_expert))
        return max(cap, self.top_k)

    def forward(self, x):
        from ....ops.manipulation import reshape
        b, s, d = x.shape
        tokens = b * s
        e, k = self.num_expert, self.top_k
        cap = self._capacity(tokens)
        xt = reshape(x, (tokens, d))
        logits, l_aux = self.gate(xt)
        self.l_aux = l_aux

        # 1) routing: pure integer work on DETACHED logits (indices carry
        #    no gradient; detaching keeps int outputs off the vjp tape).
        #    build_index_maps produces BOTH maps: token-major `slot` and
        #    expert-major `inv` — the dual maps are what let dispatch/
        #    combine and their vjps all be gathers (moe_dispatch.py).
        from ....ops.pallas.moe_dispatch import build_index_maps

        def route(lg):
            _, topi = jax.lax.top_k(lg.astype(jnp.float32), k)  # (T, K)
            slot, inv, keep = build_index_maps(topi, e, cap)
            return topi, slot, keep, inv

        topi, slot, keep, inv = apply_op(route, logits.detach())

        # 2) gate weights: differentiable in logits
        def gate_weights(lg, ti, kp):
            probs = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
            topv = jnp.take_along_axis(probs, ti, axis=-1)  # (T, K)
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)
            return jnp.where(kp.reshape(-1, k), topv, 0.0)

        gates = apply_op(gate_weights, logits, topi, keep)

        if self.dispatch_mode == "scatter":
            # r3 parity path: scatter-based dispatch (slow on TPU — the
            # scatter HLO serializes, and autodiff transposes the combine
            # gather back into a scatter-add)
            def dispatch(xv, sl):
                tok = jnp.repeat(jnp.arange(tokens), k)     # (N,)
                buf = jnp.zeros((e * cap, xv.shape[-1]), xv.dtype)
                buf = buf.at[sl].set(xv[tok], mode="drop")
                return buf.reshape(e, cap, xv.shape[-1])

            expert_in = apply_op(dispatch, xt, slot)
            expert_out = self.experts(expert_in)

            def combine(eo, g, sl):
                flat = eo.reshape(e * cap, eo.shape[-1])
                out_tk = flat.at[sl].get(mode="fill", fill_value=0)
                out_tk = out_tk * g.reshape(-1, 1).astype(flat.dtype)
                return jnp.sum(
                    out_tk.reshape(tokens, k, eo.shape[-1]), axis=1)

            out = apply_op(combine, expert_out, gates, slot)
            return reshape(out, (b, s, d))

        # 3) dispatch: expert-major row-gather via the inverse map;
        #    custom vjp keeps the backward a gather too
        from ....ops.pallas.moe_dispatch import moe_combine, moe_dispatch
        buf = apply_op(moe_dispatch, xt, inv, slot)         # (E*cap, d)
        expert_in = reshape(buf, (e, cap, d))

        # 4) the experts module — custom modules and their activation run
        #    exactly as given (E, cap, d) -> (E, cap, d)
        expert_out = self.experts(expert_in)

        # 5) combine: token-major row-gather + gate-weighted sum
        flat = reshape(expert_out, (e * cap, d))
        out = apply_op(moe_combine, flat, gates, inv, slot)
        return reshape(out, (b, s, d))

    def forward_dense(self, x):
        """Reference dense-dispatch path (one-hot (E, cap, T) tensors) kept
        for parity testing of the sort-based dispatch; O(E·cap·T) memory —
        do not use at scale."""
        from ....ops.manipulation import reshape
        b, s, d = x.shape
        tokens = b * s
        e, k = self.num_expert, self.top_k
        cap = self._capacity(tokens)
        xt = reshape(x, (tokens, d))
        logits, l_aux = self.gate(xt)
        self.l_aux = l_aux

        def build(lg):
            probs = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
            topv, topi = jax.lax.top_k(probs, k)
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)
            onehot_flat = jax.nn.one_hot(topi, e, dtype=jnp.int32)
            pos = (jnp.cumsum(onehot_flat.reshape(-1, e), axis=0)
                   * onehot_flat.reshape(-1, e) - 1)
            pos_tk = jnp.max(pos.reshape(-1, k, e), axis=-1)
            kp = (pos_tk < cap) & (pos_tk >= 0)
            gates = jnp.where(kp, topv, 0.0)
            T = lg.shape[0]
            tidx = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k))
            disp = jnp.zeros((e, cap, T), jnp.float32).at[
                topi.reshape(-1), jnp.clip(pos_tk, 0, cap - 1).reshape(-1),
                tidx.reshape(-1)].add(kp.reshape(-1).astype(jnp.float32))
            comb = jnp.zeros((e, cap, T), jnp.float32).at[
                topi.reshape(-1), jnp.clip(pos_tk, 0, cap - 1).reshape(-1),
                tidx.reshape(-1)].add(gates.reshape(-1))
            return disp, comb

        disp, comb = apply_op(build, logits)

        def dispatch(dp, xv):
            return jnp.einsum("ect,td->ecd", dp.astype(xv.dtype), xv)

        expert_in = apply_op(dispatch, disp, xt)
        expert_out = self.experts(expert_in)

        def combine(cb, eo):
            return jnp.einsum("ect,ecd->td", cb.astype(eo.dtype), eo)

        out = apply_op(combine, comb, expert_out)
        return reshape(out, (b, s, d))


# ---------------------------------------------------------------------------
# dropless top-k SwiGLU experts (the servable block)
# ---------------------------------------------------------------------------

def route_topk(logits, bias, *, top_k, scoring="sigmoid", n_group=1,
               topk_group=1, norm_topk_prob=True, scaling=1.0, forced=None):
    """The published DeepSeek-V3 router on float32 ``logits (T, E)``:
    ``s = sigmoid(logits)`` (or softmax); the CHOICE is the top-k of ``s +
    bias`` (``bias`` = ``e_score_correction_bias``, ``noaux_tc``), limited
    to the ``topk_group`` groups (of ``n_group``) whose two best biased
    scores sum highest; the WEIGHTS are ``s`` itself at the chosen experts
    (no bias), normalised over the k (``+ 1e-20``) and scaled. ``forced
    (T, k)`` replaces the choice and leaves the weights' rule alone.
    Returns ``(idx (T, k) int32, weights (T, k) float32)``."""
    logits = logits.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        t, e = choice.shape
        grouped = choice.reshape(t, n_group, e // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        keep = jax.lax.top_k(group_score, topk_group)[1]        # (T, g)
        mask = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(mask, e // n_group, axis=1),
                           choice, 0.0)
    idx = jax.lax.top_k(choice, top_k)[1] if forced is None \
        else forced.reshape(-1, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling


def dropless_expert_mix(x, idx, weights, w_gate, w_up, w_down, first=0):
    """``sum_k weights[t, k] * SwiGLU_{idx[t, k]}(x[t])`` over the experts
    HELD: the stacked ``w_gate`` / ``w_up (E_held, h, ff)`` and ``w_down
    (E_held, ff, h)`` are global experts ``first .. first + E_held - 1``;
    a pick of any other expert adds nothing here (its holder adds it).
    Exact and dropless for any routing: the ``T * k`` picks are sorted by
    expert, each expert's rows form one group of three grouped matmuls
    (``jax.lax.ragged_dot``: rows past the last group come out zero), and
    the rows return to token order by the inverse permutation — gathers
    only, no capacity, no scatter. Returns ``(y (T, h), stats)`` with
    ``stats`` int32 ``[picks on held experts, held experts with at least
    one token, largest count on one expert]``."""
    t, k = idx.shape
    e = w_gate.shape[0]
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < e)
    key = jnp.where(held, local, e)              # not held: sorted last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    xs = x[order // k]                           # (T*k, h), expert-major
    act = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes)) \
        * jax.lax.ragged_dot(xs, w_up, sizes)
    ys = jax.lax.ragged_dot(act, w_down, sizes)  # rows not held: zeros
    back = jnp.argsort(order)                    # token-major again
    w = jnp.where(held, weights.reshape(-1), 0.0).astype(ys.dtype)
    y = jnp.sum((ys[back] * w[:, None]).reshape(t, k, -1), axis=1)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                       jnp.max(sizes)]).astype(jnp.int32)
    return y, stats


class DroplessMoE(Layer):
    """Router + the routed experts this layer holds (+ nothing else: the
    always-on shared experts are the model's, counted once across
    holders). ``experts`` is ``(first, count)``: the global experts held,
    all of them by default. ``forward(x (..., h))`` returns ``(y, idx,
    stats)``; ``forced_idx`` replaces the router's choice (weights are
    still the router's scores at those experts), which is how a reference
    is held to the system's picks."""

    def __init__(self, hidden, ffn, num_experts, top_k, *, experts=None,
                 scoring="sigmoid", n_group=1, topk_group=1,
                 norm_topk_prob=True, scaling=1.0):
        super().__init__()
        from ....nn import initializer as I
        first, count = experts or (0, num_experts)
        if not (0 <= first and count > 0
                and first + count <= num_experts):
            raise ValueError(f"experts held {(first, count)} lie outside "
                             f"the router's {num_experts}")
        self.first, self.count = first, count
        self.route = dict(top_k=top_k, scoring=scoring, n_group=n_group,
                          topk_group=topk_group,
                          norm_topk_prob=norm_topk_prob, scaling=scaling)
        self.gate = Linear(hidden, num_experts, bias_attr=False)
        self.e_score_correction_bias = self.create_parameter(
            (num_experts,), dtype="float32",
            default_initializer=I.Constant(0.0))
        up = I.XavierNormal(fan_in=hidden, fan_out=ffn)
        self.gate_proj = self.create_parameter(
            (count, hidden, ffn), default_initializer=up)
        self.up_proj = self.create_parameter(
            (count, hidden, ffn), default_initializer=up)
        self.down_proj = self.create_parameter(
            (count, ffn, hidden),
            default_initializer=I.XavierNormal(fan_in=ffn, fan_out=hidden))

    def forward(self, x, forced_idx=None):
        def run(xv, gw, bias, wg, wu, wd, forced=None):
            flat = xv.reshape(-1, xv.shape[-1])
            with jax.named_scope("moe_router"):
                # float32 in earnest: a TPU runs a float32 product in
                # bf16 passes unless told otherwise, and a pick is a
                # comparison of nearly equal scores
                logits = jnp.dot(flat.astype(jnp.float32),
                                 gw.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST)
                idx, w = route_topk(logits, bias, forced=forced,
                                    **self.route)
            with jax.named_scope("moe_experts"):
                y, stats = dropless_expert_mix(flat, idx, w, wg, wu, wd,
                                               self.first)
            return y.reshape(xv.shape), idx, stats
        args = (x, self.gate.weight, self.e_score_correction_bias,
                self.gate_proj, self.up_proj, self.down_proj)
        if forced_idx is not None:
            args += (forced_idx,)
        return apply_op(run, *args)
