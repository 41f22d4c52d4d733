"""Program-transform pass infrastructure over jaxprs.

Reference parity: the PIR pass framework (paddle/pir/ PassManager +
pattern rewriter, paddle/fluid/pir/transforms/ — verify) and the
inference analysis passes (paddle/fluid/inference/analysis/ fusion
passes — verify).

TPU-native design (SURVEY §7 "PIR + passes" row): the IR is the jaxpr
(and XLA runs its own fusion pipeline downstream, so passes here are for
things XLA can't or won't do at the jaxpr level): dead-code elimination
before lowering (smaller programs compile faster), constant folding,
program statistics for cost tooling, and layer-level inference rewrites
(conv+BN folding). A pass is ``ClosedJaxpr -> ClosedJaxpr``;
``PassManager`` composes them and ``apply_passes`` wraps a python
callable so the transformed program is what jit compiles.
"""
from __future__ import annotations

import collections
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
from jax.extend.core import (ClosedJaxpr, Jaxpr, JaxprEqn,
                             Literal, Var)

__all__ = ["PassManager", "apply_passes", "dce_pass", "fold_constants",
           "program_stats", "fuse_conv_bn", "default_pipeline",
           "cse_pass", "fusion_pass", "inline_pjit", "fusion_enabled"]


def fusion_enabled() -> bool:
    """Default-off kill switch for the reduction-fusion fast paths
    (``PT_FUSION_PASSES=1`` turns them on). Read at call/trace time so
    tests and benches can A/B without re-importing."""
    from ..utils.flags import env_flag
    return env_flag("PT_FUSION_PASSES")


# ---------------------------------------------------------------------------
# pass framework
# ---------------------------------------------------------------------------

class PassManager:
    """Ordered pass pipeline (reference: pir::PassManager — verify).
    Each pass runs under a ``RecordEvent("pass:<name>")`` profiler span;
    per-pass eqn counts land in ``self.last_stats``."""

    def __init__(self, passes: Sequence[Callable] = ()):
        self._passes: List[Callable] = list(passes)
        self.last_stats: List[dict] = []

    def add_pass(self, p: Callable):
        self._passes.append(p)
        return self

    @staticmethod
    def _name(p) -> str:
        return getattr(p, "pass_name", getattr(p, "__name__",
                                               type(p).__name__))

    def run(self, closed: ClosedJaxpr) -> ClosedJaxpr:
        from ..observability import metrics as om
        from ..profiler import RecordEvent
        self.last_stats = []
        for p in self._passes:
            before = len(closed.jaxpr.eqns)
            with RecordEvent(f"pass:{self._name(p)}"):
                closed = p(closed)
            after = len(closed.jaxpr.eqns)
            self.last_stats.append({"pass": self._name(p),
                                    "eqns_before": before,
                                    "eqns_after": after})
            om.counter("pt_passes_runs_total", "pass executions",
                       labels=("pass",)).inc(**{"pass": self._name(p)})
            if after < before:
                om.counter("pt_passes_eqns_removed_total",
                           "jaxpr equations removed, by pass",
                           labels=("pass",)).inc(
                    before - after, **{"pass": self._name(p)})
        return closed

    def __call__(self, closed: ClosedJaxpr) -> ClosedJaxpr:
        return self.run(closed)


def default_pipeline() -> List[Callable]:
    """The standard optimization pipeline, outermost-enabling first:
    inline pjit bodies (expose library-fn internals), fold constants
    (turn shape-arithmetic into literals the matchers can pin), CSE
    (canonicalize duplicate chains into graph identities), reduction
    fusion, then DCE to sweep the dead interiors."""
    from .cse import cse_pass
    from .fusion import fusion_pass
    from .patterns import inline_pjit
    return [inline_pjit, fold_constants, cse_pass, fusion_pass, dce_pass]


def apply_passes(fn: Callable, *example_args, passes: Sequence[Callable]):
    """Trace ``fn``, run the pass pipeline on its jaxpr, and return a
    callable evaluating the TRANSFORMED program (jit-compatible)."""
    closed = jax.make_jaxpr(fn)(*example_args)
    closed = PassManager(passes).run(closed)

    def transformed(*args):
        out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)
        return out[0] if len(out) == 1 else tuple(out)
    return transformed


def _rebuild(closed: ClosedJaxpr, eqns: List[JaxprEqn],
             constvars=None, consts=None) -> ClosedJaxpr:
    jaxpr = closed.jaxpr
    # propagate the source jaxpr's debug_info: constructing a Jaxpr
    # without one is deprecated (and was the suite's loudest warning)
    new_jaxpr = Jaxpr(constvars=jaxpr.constvars if constvars is None
                      else constvars,
                      invars=jaxpr.invars,
                      outvars=jaxpr.outvars, eqns=eqns,
                      effects=jaxpr.effects,
                      debug_info=jaxpr.debug_info)
    return ClosedJaxpr(new_jaxpr,
                       closed.consts if consts is None else consts)


# ---------------------------------------------------------------------------
# passes
# ---------------------------------------------------------------------------

def dce_pass(closed: ClosedJaxpr) -> ClosedJaxpr:
    """Dead-code elimination: drop equations whose outputs are never
    used (reference: pir dead_code_elimination_pass — verify). Smaller
    jaxprs lower and compile faster; XLA would also DCE, but only after
    paying lowering cost for the dead ops."""
    jaxpr = closed.jaxpr
    live = {v for v in jaxpr.outvars if isinstance(v, Var)}
    kept: List[JaxprEqn] = []
    for eqn in reversed(jaxpr.eqns):
        if eqn.effects or any(isinstance(o, Var) and o in live
                              for o in eqn.outvars):
            kept.append(eqn)
            for i in eqn.invars:
                if isinstance(i, Var):
                    live.add(i)
    kept.reverse()
    return _rebuild(closed, kept)


_FOLDABLE = {"sin", "cos", "exp", "log", "sqrt", "rsqrt", "tanh", "neg",
             "add", "sub", "mul", "div", "max", "min", "pow",
             "integer_pow", "convert_element_type", "sign", "floor",
             "ceil"}


def fold_constants(closed: ClosedJaxpr) -> ClosedJaxpr:
    """Constant folding: evaluate foldable equations whose inputs are
    all literals/consts at pass time (reference: pir
    constant_folding_pass — verify).

    Scalar folded values splice back in as Literals. Non-scalar folded
    values (and any folded value that feeds a jaxpr outvar, where a
    Literal is not a legal binder) splice back in as CONSTVARS — the
    folded eqn's outvar simply moves to the constvar list with its
    computed value, so every downstream reference stays valid. The old
    implementation dropped the producing eqn but left non-scalar uses
    pointing at a var nothing produced."""
    jaxpr = closed.jaxpr
    known = dict(zip(jaxpr.constvars, closed.consts))
    folded = {}                     # Var (eqn outvar) -> computed value
    new_eqns: List[JaxprEqn] = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name in _FOLDABLE and not eqn.effects
                and len(eqn.outvars) == 1
                and all(isinstance(i, Literal) or i in known
                        or i in folded for i in eqn.invars)):
            vals = [i.val if isinstance(i, Literal)
                    else known[i] if i in known else folded[i]
                    for i in eqn.invars]
            out = eqn.primitive.bind(*vals, **eqn.params)
            folded[eqn.outvars[0]] = out
            continue
        # scalar known values become inline Literals
        new_invars = [
            Literal(known[i] if i in known else folded[i], i.aval)
            if (isinstance(i, Var) and (i in known or i in folded)
                and not i.aval.shape)
            else i
            for i in eqn.invars]
        new_eqns.append(eqn.replace(invars=new_invars))
    # NOTE: even with nothing folded, new_eqns may carry scalar
    # constvar->Literal substitutions the fusion matchers depend on
    # (Lit patterns only match Literal atoms) — always rebuild.
    # Folded vars still referenced (non-scalar uses, or outvars — a
    # jaxpr output must stay a var) re-bind as constvars
    still_used = {i for e in new_eqns for i in e.invars
                  if isinstance(i, Var)}
    out_set = {o for o in jaxpr.outvars if isinstance(o, Var)}
    new_constvars = list(jaxpr.constvars)
    new_consts = list(closed.consts)
    for v, val in folded.items():
        if v in still_used or v in out_set:
            new_constvars.append(v)
            new_consts.append(val)
    return dce_pass(_rebuild(closed, new_eqns, constvars=new_constvars,
                             consts=new_consts))


def program_stats(closed: ClosedJaxpr) -> dict:
    """Per-primitive op counts + totals (reference: the pir program
    statistics used by cost tooling — verify)."""
    counts = collections.Counter(
        e.primitive.name for e in closed.jaxpr.eqns)
    return {"n_eqns": len(closed.jaxpr.eqns),
            "n_invars": len(closed.jaxpr.invars),
            "primitives": dict(counts)}


# ---------------------------------------------------------------------------
# layer-level inference rewrites
# ---------------------------------------------------------------------------

def fuse_conv_bn(model):
    """Fold BatchNorm into the preceding Conv2D for inference
    (reference: inference analysis conv_bn_fuse_pass — verify): replaces
    W with W·γ/σ and b with (b-μ)·γ/σ+β, then the BN becomes identity.
    Works on any Layer whose sublayer sequence contains Conv2D→BN pairs
    (nn.Sequential or custom with ordered _sub_layers). Returns the
    model, mutated in place; call under .eval() semantics."""
    from ..nn.conv import Conv2D
    from ..nn.norm import BatchNorm2D, _BatchNormBase

    def fold(conv, bn):
        import numpy as np
        eps = bn.epsilon
        gamma = bn.weight._value
        beta = bn.bias._value
        mu = bn._mean._value
        var = bn._variance._value
        scale = gamma / jnp.sqrt(var + eps)
        w = conv.weight._value * scale.reshape(-1, 1, 1, 1)
        conv.weight._update_value(w)
        if conv.bias is None:
            from ..tensor import Parameter
            conv.bias = Parameter(jnp.zeros((w.shape[0],), w.dtype))
        b = (conv.bias._value - mu) * scale + beta
        conv.bias._update_value(b)
        # neutralize the BN: identity transform
        bn.weight._update_value(jnp.ones_like(gamma))
        bn.bias._update_value(jnp.zeros_like(beta))
        bn._mean._update_value(jnp.zeros_like(mu))
        bn._variance._update_value(jnp.ones_like(var) - eps)

    def walk(layer):
        subs = list(layer._sub_layers.values())
        for a, b in zip(subs, subs[1:]):
            if isinstance(a, Conv2D) and isinstance(b, _BatchNormBase):
                fold(a, b)
        for s in subs:
            walk(s)
    walk(model)
    return model


# re-exported pipeline passes (import last: cse/fusion pull in patterns,
# which lazily imports this module's _rebuild)
from .cse import cse_pass            # noqa: E402,F401
from .fusion import fusion_pass      # noqa: E402,F401
from .patterns import inline_pjit    # noqa: E402,F401
