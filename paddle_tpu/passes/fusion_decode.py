"""Decode-layer fusion: the rule family that recognizes a marked
attention→o_proj→MLP decode layer inside the serving decode-block
jaxpr and splices the single fused "decode layer" call
(ops/pallas/decode_layer.py).

Extends the PR 3 pass machinery in two ways the reduction rules never
needed:

- **sub-jaxpr recursion** (:func:`rewrite_everywhere`): the decode
  block is a ``lax.scan`` over block steps, so the layers live inside
  the scan's body jaxpr — the rewriter descends into every
  Jaxpr/ClosedJaxpr-valued eqn param (scan/while/cond/pjit/closed_call)
  and rebuilds the enclosing eqn bottom-up;
- **multi-output splice**: a decode layer returns the hidden state
  PLUS the updated KV arenas (2 or 4 arrays), so the replacement
  ``closed_call`` carries every outvar of the matched region
  (patterns.make_rewrite_pass only splices single-output roots).

Recognition is anchor + certificate, not a 200-primitive tree: the
anchor is the ``pt_decode_layer_<mode>`` pjit equation the model emits
under :func:`ops.pallas.decode_layer.marking` (arity and literal-eps
checked against the documented ARG_LAYOUT), and the certificate
re-runs the patterns machinery over the region's own (pjit-inlined)
body to prove the attention→o_proj→MLP chain is really there — the
SwiGLU tail is matched structurally (add(h, dot(silu(gate)·up, wd))),
the attention/norm half by primitive census (the qkv/o/MLP
dot_generals, both rsqrt folds). A marked region that fails the
certificate is left unfused (and counted), never rewritten on faith.

Rewrites land in ``pt_passes_rewrites_total{rule="decode_layer"}`` like
every other fusion rule.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.core as jcore
from jax.extend.core import ClosedJaxpr, Jaxpr, JaxprEqn, Literal
from jax.extend.core.primitives import closed_call_p, jit_p

from .patterns import AnyPat, Bind, EqnGraph, MatchState, Or, Prim

__all__ = ["decode_fusion_pass", "make_decode_fusion_pass",
           "rewrite_everywhere", "fused_decode_calls",
           "walk_outside_fused", "FUSED_CALL_NAME"]

MARK_PREFIX = "pt_decode_layer_"
FUSED_CALL_NAME = "pt_fused_decode_layer"
RULE_NAME = "decode_layer"


# ---------------------------------------------------------------------------
# generic sub-jaxpr rewriting (scan/while/cond/pjit bodies)
# ---------------------------------------------------------------------------

def _rewrite_jaxpr(jaxpr: Jaxpr, eqn_fn: Callable, skip_into=None):
    changed = False
    new_eqns = []
    for eqn in jaxpr.eqns:
        if skip_into is None or not skip_into(eqn):
            new_params = None
            for k, v in eqn.params.items():
                if isinstance(v, ClosedJaxpr):
                    nj, ch = _rewrite_jaxpr(v.jaxpr, eqn_fn, skip_into)
                    if ch:
                        new_params = dict(new_params or eqn.params)
                        new_params[k] = ClosedJaxpr(nj, v.consts)
                elif isinstance(v, Jaxpr):
                    nj, ch = _rewrite_jaxpr(v, eqn_fn, skip_into)
                    if ch:
                        new_params = dict(new_params or eqn.params)
                        new_params[k] = nj
                elif isinstance(v, (tuple, list)) and v and all(
                        isinstance(x, (Jaxpr, ClosedJaxpr)) for x in v):
                    subs, any_ch = [], False
                    for x in v:
                        inner = x.jaxpr if isinstance(x, ClosedJaxpr) \
                            else x
                        nj, ch = _rewrite_jaxpr(inner, eqn_fn, skip_into)
                        any_ch |= ch
                        subs.append(ClosedJaxpr(nj, x.consts)
                                    if isinstance(x, ClosedJaxpr) else nj)
                    if any_ch:
                        new_params = dict(new_params or eqn.params)
                        new_params[k] = type(v)(subs)
                if new_params is not None and k in new_params:
                    changed = True
            if new_params is not None:
                eqn = eqn.replace(params=new_params)
        new = eqn_fn(eqn)
        if new is not eqn:
            changed = True
        new_eqns.append(new)
    if not changed:
        return jaxpr, False
    return Jaxpr(constvars=jaxpr.constvars, invars=jaxpr.invars,
                 outvars=jaxpr.outvars, eqns=new_eqns,
                 effects=jaxpr.effects,
                 debug_info=jaxpr.debug_info), True


def rewrite_everywhere(closed: ClosedJaxpr, eqn_fn: Callable,
                       skip_into=None) -> ClosedJaxpr:
    """Apply ``eqn_fn(eqn) -> eqn`` to every equation of ``closed``,
    recursing into all Jaxpr-valued params (scan/while/cond/pjit/
    closed_call bodies) bottom-up. ``skip_into(eqn)`` prunes descent
    (the no-transient walks use it to treat fused calls as opaque)."""
    nj, ch = _rewrite_jaxpr(closed.jaxpr, eqn_fn, skip_into)
    return ClosedJaxpr(nj, closed.consts) if ch else closed


def walk_eqns(jaxpr: Jaxpr, skip_into=None):
    """Yield every eqn recursively (same descent as
    :func:`rewrite_everywhere`, read-only)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if skip_into is not None and skip_into(eqn):
            continue
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for x in vs:
                if isinstance(x, ClosedJaxpr):
                    yield from walk_eqns(x.jaxpr, skip_into)
                elif isinstance(x, Jaxpr):
                    yield from walk_eqns(x, skip_into)


# ---------------------------------------------------------------------------
# fused-call identification (shared by tests/bench walks)
# ---------------------------------------------------------------------------

def is_fused_decode_call(eqn: JaxprEqn) -> bool:
    if eqn.primitive.name != "closed_call":
        return False
    cj = eqn.params.get("call_jaxpr")
    if not isinstance(cj, ClosedJaxpr):
        return False
    di = getattr(cj.jaxpr, "debug_info", None)
    src = getattr(di, "func_src_info", None) or \
        getattr(di, "func_name", None) or ""
    return FUSED_CALL_NAME in str(src)


def fused_decode_calls(closed: ClosedJaxpr):
    """Every fused decode-layer closed_call in the program (recursive,
    not descending into the calls themselves)."""
    return [e for e in walk_eqns(closed.jaxpr,
                                 skip_into=is_fused_decode_call)
            if is_fused_decode_call(e)]


def walk_outside_fused(closed: ClosedJaxpr):
    """Every eqn OUTSIDE fused decode-layer calls — the no-transient
    claim's domain: shapes produced here round-trip HBM between XLA
    ops; values inside a fused call are the kernel's VMEM residents
    (off-TPU the call body mirrors the math — the walk's contract is
    about the fused program structure, pinned in tests/bench)."""
    for eqn in walk_eqns(closed.jaxpr, skip_into=is_fused_decode_call):
        if not is_fused_decode_call(eqn):
            yield eqn


# ---------------------------------------------------------------------------
# the certificate: prove the marked region is the decode-layer chain
# ---------------------------------------------------------------------------

# SwiGLU tail, matched structurally on the region's inlined body:
#   out = add(h, dot(mul(mul(g, logistic(g)), dot(r2, wu)), wd))
# (jax.nn.silu traces as mul(x, logistic(x)); Bind asserts both reads
# are ONE graph value.)
_silu = Or(
    Prim("mul", Bind("g", AnyPat()), Prim("logistic", Bind("g", AnyPat()))),
    Prim("mul", Prim("logistic", Bind("g", AnyPat())), Bind("g", AnyPat())))
_MLP_TAIL = Prim(
    "add",
    AnyPat(),
    Prim("dot_general",
         Prim("mul", _silu, Prim("dot_general", AnyPat(), AnyPat())),
         AnyPat()))


def _certify_body(inner: ClosedJaxpr, mode: str, x_aval) -> bool:
    """The marked region must really be one decode layer: census over
    the inlined body (>= 7 dot_generals: q/k/v, o_proj, gate/up/down;
    both RMS rsqrt folds; a silu) plus a structural match of the SwiGLU
    residual tail anchored at the hidden-state output."""
    from .patterns import inline_pjit
    try:
        flat = inline_pjit(inner)
    except Exception:
        return False
    names = [e.primitive.name for e in walk_eqns(flat.jaxpr)]
    if sum(n == "dot_general" for n in names) < 7:
        return False
    if sum(n == "rsqrt" for n in names) < 2:
        return False
    if "logistic" not in names:
        return False
    out0 = flat.jaxpr.outvars[0]
    if tuple(out0.aval.shape) != tuple(x_aval.shape):
        return False
    graph = EqnGraph(flat.jaxpr)
    return _MLP_TAIL.match(graph, out0, MatchState())


def _validate_marked(eqn: JaxprEqn) -> Optional[tuple]:
    """Parse + validate a marked pjit eqn; returns (mode, inner_closed,
    eps1, eps2) or None to decline."""
    from ..ops.pallas.decode_layer import N_CACHE, N_FIXED, N_WEIGHTS
    name = str(eqn.params.get("name", ""))
    if not name.startswith(MARK_PREFIX):
        return None
    mode = name[len(MARK_PREFIX):]
    if mode not in N_CACHE:
        return None
    inner = eqn.params.get("jaxpr")
    if not isinstance(inner, ClosedJaxpr) or eqn.effects:
        return None
    nc = N_CACHE[mode]
    if len(eqn.invars) != N_FIXED + nc + N_WEIGHTS:
        return None
    if len(eqn.outvars) != 1 + nc:
        return None
    e1, e2 = eqn.invars[3], eqn.invars[4]
    if not (isinstance(e1, Literal) and isinstance(e2, Literal)):
        return None
    x_aval = eqn.invars[0].aval
    if x_aval.ndim != 3 or x_aval.shape[1] != 1:
        return None
    if not _certify_body(inner, mode, x_aval):
        return None
    return mode, inner, float(e1.val), float(e2.val)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def _record(rule_name: str):
    from ..observability import metrics as om
    om.counter("pt_passes_rewrites_total",
               "fusion-rule rewrites applied, by rule",
               labels=("rule",)).inc(rule=rule_name)


def make_decode_fusion_pass(allow_kernel: bool = True):
    """Build the decode-layer fusion pass. ``allow_kernel=False`` keeps
    the splice (and therefore the fused-call program structure) but
    pins the off-TPU/captured-jaxpr body even on TPU — the weight-quant
    engines use it so XLA's dequant-into-gemm prologue fusion is never
    traded for an HBM-materialized fp32 weight."""
    from ..ops.pallas.decode_layer import build_fused_callable

    def run(closed: ClosedJaxpr) -> ClosedJaxpr:
        stats = run.last_rewrites = {}

        def eqn_fn(eqn: JaxprEqn) -> JaxprEqn:
            if eqn.primitive is not jit_p:
                return eqn
            parsed = _validate_marked(eqn)
            if parsed is None:
                if str(eqn.params.get("name", "")).startswith(
                        MARK_PREFIX):
                    stats["declined"] = stats.get("declined", 0) + 1
                return eqn
            mode, inner, eps1, eps2 = parsed
            fn = build_fused_callable(mode, inner, eps1, eps2,
                                      allow_kernel=allow_kernel)
            specs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                     for v in eqn.invars]
            try:
                traced = jax.make_jaxpr(fn)(*specs)
            except Exception:
                stats["declined"] = stats.get("declined", 0) + 1
                return eqn
            want = [(tuple(o.aval.shape), o.aval.dtype)
                    for o in eqn.outvars]
            got = [(tuple(a.shape), a.dtype) for a in traced.out_avals]
            if want != got:
                stats["declined"] = stats.get("declined", 0) + 1
                return eqn
            stats[RULE_NAME] = stats.get(RULE_NAME, 0) + 1
            stats["kernel"] = stats.get("kernel", 0) + int(
                getattr(fn, "uses_kernel", False))
            _record(RULE_NAME)
            return jcore.new_jaxpr_eqn(
                list(eqn.invars), list(eqn.outvars), closed_call_p,
                dict(call_jaxpr=traced), traced.effects)

        return rewrite_everywhere(closed, eqn_fn)

    run.last_rewrites = {}
    run.pass_name = "fusion_decode"
    return run


# the default pipeline instance (kernel allowed; engines with in-graph
# weight dequant build their own via make_decode_fusion_pass(False))
decode_fusion_pass = make_decode_fusion_pass()
