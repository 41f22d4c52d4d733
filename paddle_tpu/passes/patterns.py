"""Jaxpr subgraph pattern matching + rewrite-rule infrastructure.

Reference parity: the PIR pattern rewriter (paddle/pir/ DrrPatternBase /
RewritePattern + PatternApplicator — verify). The PIR rewriter matches a
declarative op DAG against the program and splices in a replacement op;
here the IR is the jaxpr, so a pattern is a small tree of primitive
matchers walked up the def-use chain from an anchor equation, and a
rewrite replaces the matched root with ONE ``closed_call`` equation
whose ``call_jaxpr`` is the traced fused implementation. The interior of
the matched subgraph is left in place and falls to DCE when nothing
else uses it — an interior value with outside users keeps its original
producer, so overlapping matches can never break semantics.

``closed_call`` was chosen over inlining the fused body because it (a)
keeps the rewrite O(1) eqns with no var renaming, (b) survives jit /
grad / vmap (the primitive has full rules), and (c) preserves any
``custom_vjp`` inside the fused implementation — which is exactly how
the Pallas softmax-cross-entropy kernel ships its hand-written
backward (see passes/fusion.py).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.core as jcore
from jax.extend.core import ClosedJaxpr, Jaxpr, JaxprEqn, Literal, Var
from jax.extend.core.primitives import closed_call_p, jit_p

__all__ = ["EqnGraph", "MatchState", "Pat", "AnyPat", "Capture", "Bind",
           "Lit", "Prim", "Or", "maybe_cast", "RewriteRule",
           "make_rewrite_pass", "inline_pjit"]

Atom = Union[Var, Literal]


# ---------------------------------------------------------------------------
# def-use graph
# ---------------------------------------------------------------------------

class EqnGraph:
    """Def/use index over one jaxpr: ``producer(var)`` is the eqn whose
    outvars contain it (None for invars/constvars)."""

    def __init__(self, jaxpr: Jaxpr):
        self.jaxpr = jaxpr
        self._def: Dict[Var, JaxprEqn] = {}
        for eqn in jaxpr.eqns:
            for o in eqn.outvars:
                if isinstance(o, Var):
                    self._def[o] = eqn

    def producer(self, atom: Atom) -> Optional[JaxprEqn]:
        if isinstance(atom, Var):
            return self._def.get(atom)
        return None


def _is_neg_inf_lit(atom: Atom) -> bool:
    if not isinstance(atom, Literal):
        return False
    try:
        v = np.asarray(atom.val)
        return v.ndim == 0 and np.isneginf(v)
    except (TypeError, ValueError):
        return False


# value-preserving wrapper ops the matcher walks through: broadcasts,
# gradient annotations, and the ``max(x, -inf)`` clamp jax.nn.softmax
# inserts for empty-reduction safety. stop_gradient is skipped ONLY
# during structural (Prim) walks — the patterns that rely on it
# (softmax/log_softmax subtract a stop_gradient'd max) are
# shift-invariant, so dropping that internal annotation is exact. A
# CAPTURE must never bind across stop_gradient: the bound atom becomes
# the fused call's input, and skipping would silently re-enable
# gradients the original program blocked (target networks,
# straight-through estimators).
def _bcast_kind(eqn) -> str:
    """Classify a broadcast_in_dim by where it puts the operand:

    - "keepdims": operand dims stay leading, size-1 dims appended
      (what reduce+keepdims re-expansion traces as)
    - "leading":  operand aligned to the TRAILING axes, size-1 dims
      prepended (numpy-style w[None, :] weight broadcasting)
    - "scalar":   0-d operand (unambiguous)
    - "other":    anything else — e.g. (n,) -> (1, n) used against a
      ROW-reduced value; skipping those rewrote column-normalizations
      into softmax on square inputs, so they are never skipped.
    """
    op = eqn.invars[0]
    ishape = tuple(op.aval.shape)
    n = len(ishape)
    if n == 0:
        return "scalar"
    dims = tuple(eqn.params.get("broadcast_dimensions", ()))
    oshape = tuple(eqn.outvars[0].aval.shape)
    out_n = len(oshape)
    if (dims == tuple(range(n)) and oshape[:n] == ishape
            and all(d == 1 for d in oshape[n:])):
        return "keepdims"
    if (dims == tuple(range(out_n - n, out_n))
            and oshape[out_n - n:] == ishape
            and all(d == 1 for d in oshape[:out_n - n])):
        return "leading"
    return "other"


def _skip_transparent(graph: EqnGraph, atom: Atom,
                      through_cast: bool = False,
                      for_binding: bool = False) -> Atom:
    while isinstance(atom, Var):
        eqn = graph.producer(atom)
        if eqn is None:
            break
        name = eqn.primitive.name
        if name == "broadcast_in_dim":
            # structural walks only cross reduce-keepdims re-expansions;
            # bindings only cross numpy-trailing weight broadcasts (the
            # alignment the fused impls re-apply). Everything else is
            # semantics-bearing and blocks the walk.
            kind = _bcast_kind(eqn)
            ok = kind == "scalar" or \
                (kind == "leading" if for_binding else kind == "keepdims")
            if not ok:
                break
            atom = eqn.invars[0]
            continue
        if name == "copy":
            atom = eqn.invars[0]
            continue
        if name == "stop_gradient" and not for_binding:
            atom = eqn.invars[0]
            continue
        if name == "max" and any(_is_neg_inf_lit(i) for i in eqn.invars):
            atom = next(i for i in eqn.invars if not _is_neg_inf_lit(i))
            continue
        if through_cast and name == "convert_element_type":
            atom = eqn.invars[0]
            continue
        break
    return atom


# ---------------------------------------------------------------------------
# match state + patterns
# ---------------------------------------------------------------------------

class MatchState:
    """Bindings collected during one match attempt. ``bindings`` maps
    capture names to atoms; ``linked`` maps link names to matched eqns
    (for builders that need primitive params, e.g. reduce axes)."""

    def __init__(self):
        self.bindings: Dict[str, Atom] = {}
        self.linked: Dict[str, JaxprEqn] = {}
        self.eqns: List[JaxprEqn] = []

    def _snapshot(self):
        return (dict(self.bindings), dict(self.linked), len(self.eqns))

    def _restore(self, snap):
        self.bindings, self.linked, n = snap[0], snap[1], snap[2]
        del self.eqns[n:]


def _same_atom(a: Atom, b: Atom) -> bool:
    if isinstance(a, Var) or isinstance(b, Var):
        return a is b
    try:
        return (np.shape(a.val) == np.shape(b.val)
                and bool(np.all(np.asarray(a.val) == np.asarray(b.val))))
    except (TypeError, ValueError):
        return False


class Pat:
    def match(self, graph: EqnGraph, atom: Atom, st: MatchState) -> bool:
        raise NotImplementedError


class AnyPat(Pat):
    """Wildcard: matches any atom, binds nothing."""

    def match(self, graph, atom, st):
        return True


class Capture(Pat):
    """Bind the atom (pre-broadcast/-annotation) under ``name``. A second
    occurrence of the same name must resolve to the SAME atom — that is
    how e.g. the softmax pattern asserts both ``sub`` and ``reduce_max``
    read one input. ``through_cast`` also walks through
    convert_element_type, for patterns whose fused impl re-applies the
    cast internally (rms_norm fp32 accumulation)."""

    def __init__(self, name: str, through_cast: bool = False):
        self.name = name
        self.through_cast = through_cast

    def match(self, graph, atom, st):
        atom = _skip_transparent(graph, atom, self.through_cast,
                                 for_binding=True)
        prev = st.bindings.get(self.name)
        if prev is not None:
            return _same_atom(prev, atom)
        st.bindings[self.name] = atom
        return True


class Bind(Pat):
    """Match ``inner`` against the atom and bind the atom under
    ``name``. A SECOND occurrence of the name short-circuits to an
    identity check against the first binding — this is how a pattern
    asserts two uses read the same value (e.g. softmax's numerator and
    denominator share one ``exp``)."""

    def __init__(self, name: str, inner: Pat, through_cast: bool = False):
        self.name = name
        self.inner = inner
        self.through_cast = through_cast

    def match(self, graph, atom, st):
        atom = _skip_transparent(graph, atom, self.through_cast,
                                 for_binding=True)
        prev = st.bindings.get(self.name)
        if prev is not None:
            return _same_atom(prev, atom)
        snap = st._snapshot()
        if not self.inner.match(graph, atom, st):
            st._restore(snap)
            return False
        st.bindings[self.name] = atom
        return True


class Lit(Pat):
    """Match a Literal; ``value`` pins it, ``name`` binds the value."""

    def __init__(self, value=None, name: Optional[str] = None):
        self.value = value
        self.name = name

    def match(self, graph, atom, st):
        atom = _skip_transparent(graph, atom)
        if not isinstance(atom, Literal):
            return False
        try:
            val = np.asarray(atom.val)
        except (TypeError, ValueError):
            return False
        if val.ndim != 0:
            return False
        if self.value is not None and not np.isclose(
                float(val), float(self.value)):
            return False
        if self.name is not None:
            prev = st.bindings.get(self.name)
            if prev is not None:
                return _same_atom(prev, atom)
            st.bindings[self.name] = atom
        return True


class Prim(Pat):
    """Match the producing equation of an atom by primitive name(s),
    then recursively match its inputs positionally. ``params`` entries
    are equality (or predicate) constraints on eqn.params; ``link``
    exposes the matched eqn to the builder."""

    def __init__(self, name, *ins: Pat, params: Optional[dict] = None,
                 link: Optional[str] = None, through_cast: bool = False):
        self.names = (name,) if isinstance(name, str) else tuple(name)
        self.ins = ins
        self.params = params
        self.link = link
        self.through_cast = through_cast

    def match(self, graph, atom, st):
        snap = st._snapshot()
        atom = _skip_transparent(graph, atom, self.through_cast)
        eqn = graph.producer(atom)
        if (eqn is None or eqn.primitive.name not in self.names
                or len(eqn.outvars) != 1):
            return False
        if self.params:
            for k, want in self.params.items():
                got = eqn.params.get(k)
                ok = want(got) if callable(want) else got == want
                if not ok:
                    st._restore(snap)
                    return False
        if self.ins:
            if len(eqn.invars) < len(self.ins):
                return False
            for p, a in zip(self.ins, eqn.invars):
                if not p.match(graph, a, st):
                    st._restore(snap)
                    return False
        st.eqns.append(eqn)
        if self.link is not None:
            st.linked[self.link] = eqn
        return True


class Or(Pat):
    """First matching alternative wins; failed alternatives roll back
    their partial bindings."""

    def __init__(self, *alts: Pat):
        self.alts = alts

    def match(self, graph, atom, st):
        for alt in self.alts:
            snap = st._snapshot()
            if alt.match(graph, atom, st):
                return True
            st._restore(snap)
        return False


def maybe_cast(p: Pat) -> Pat:
    """Pattern combinator: ``p`` optionally wrapped in one
    convert_element_type (mixed-precision variants of a subgraph)."""
    return Or(Prim("convert_element_type", p), p)


# ---------------------------------------------------------------------------
# rewrite rules
# ---------------------------------------------------------------------------

class RewriteRule:
    """``pattern`` anchored at a root eqn; ``build(state, root_eqn)``
    returns ``(fused_fn, arg_atoms)`` or None to decline after
    inspecting bindings (shape/axis/dtype validation lives there)."""

    def __init__(self, name: str, pattern: Pat,
                 build: Callable[[MatchState, JaxprEqn],
                                 Optional[Tuple[Callable, Sequence[Atom]]]]):
        self.name = name
        self.pattern = pattern
        # root primitive names the pattern can anchor on (fast pre-filter)
        self.roots = pattern.names if isinstance(pattern, Prim) else None
        self.build = build


def _trace_replacement(fn, args: Sequence[Atom], root: JaxprEqn):
    """Trace ``fn`` at the arg avals; decline (None) when the traced
    output aval does not exactly match the root eqn's output."""
    specs = [jax.ShapeDtypeStruct(a.aval.shape, a.aval.dtype) for a in args]
    try:
        inner = jax.make_jaxpr(fn)(*specs)
    except Exception:
        return None
    if len(inner.out_avals) != 1:
        return None
    out = inner.out_avals[0]
    want = root.outvars[0].aval
    if out.shape != want.shape or out.dtype != want.dtype:
        return None
    return inner


def make_rewrite_pass(rules: Sequence[RewriteRule], pass_name: str = "fusion",
                      on_rewrite: Optional[Callable] = None):
    """Build a ClosedJaxpr->ClosedJaxpr pass applying ``rules``.

    Equations are scanned in REVERSE (outermost roots first) so a large
    pattern (softmax-xent) claims its interior before a smaller one
    (log_softmax) anchors on an inner eqn; eqns consumed by an accepted
    rewrite are skipped as roots. Dead interior is left for dce_pass
    (run it after this pass)."""
    def run(closed: ClosedJaxpr) -> ClosedJaxpr:
        from . import _rebuild  # late: avoid import cycle
        jaxpr = closed.jaxpr
        graph = EqnGraph(jaxpr)
        consumed: set = set()
        replacement: Dict[int, JaxprEqn] = {}
        for eqn in reversed(jaxpr.eqns):
            if id(eqn) in consumed or eqn.effects:
                continue
            for rule in rules:
                if rule.roots is not None and \
                        eqn.primitive.name not in rule.roots:
                    continue
                st = MatchState()
                if not rule.pattern.match(graph, eqn.outvars[0], st):
                    continue
                built = rule.build(st, eqn)
                if built is None:
                    continue
                fn, args = built
                inner = _trace_replacement(fn, args, eqn)
                if inner is None:
                    continue
                replacement[id(eqn)] = jcore.new_jaxpr_eqn(
                    list(args), list(eqn.outvars), closed_call_p,
                    dict(call_jaxpr=inner), inner.effects)
                consumed.update(id(e) for e in st.eqns)
                if on_rewrite is not None:
                    on_rewrite(rule.name, eqn)
                break
        if not replacement:
            return closed
        new_eqns = [replacement.get(id(e), e) for e in jaxpr.eqns]
        return _rebuild(closed, new_eqns)

    run.pass_name = pass_name
    return run


# ---------------------------------------------------------------------------
# pjit inlining
# ---------------------------------------------------------------------------

def inline_pjit(closed: ClosedJaxpr, max_rounds: int = 5) -> ClosedJaxpr:
    """Splice ``pjit`` call bodies inline (to fixpoint over nesting).

    jnp/nn library functions trace as pjit-wrapped sub-jaxprs
    (log_softmax, var, take_along_axis, ...); the pattern matcher works
    on flat primitive chains, so this runs FIRST in the pipeline.
    Effectful pjits are left in place."""
    for _ in range(max_rounds):
        if not any(e.primitive is jit_p and not e.effects
                   for e in closed.jaxpr.eqns):
            break
        closed = _inline_one_level(closed)
    return closed


def _inline_one_level(closed: ClosedJaxpr) -> ClosedJaxpr:
    jaxpr = closed.jaxpr
    constvars = list(jaxpr.constvars)
    consts = list(closed.consts)
    # one constvar per distinct const object: N inlined call sites of
    # the same library fn must not append N copies of its closure const
    const_of: Dict[int, Var] = {id(c): v
                                for v, c in zip(constvars, consts)}
    subst: Dict[Var, Atom] = {}

    def res(atom: Atom) -> Atom:
        while isinstance(atom, Var) and atom in subst:
            atom = subst[atom]
        return atom

    out_eqns: List[JaxprEqn] = []
    for eqn in jaxpr.eqns:
        eqn = eqn.replace(invars=[res(i) for i in eqn.invars])
        inner = eqn.params.get("jaxpr") if eqn.primitive is jit_p \
            else None
        if inner is None or eqn.effects or not isinstance(inner, ClosedJaxpr):
            out_eqns.append(eqn)
            continue
        ij = inner.jaxpr
        m: Dict[Var, Atom] = {}
        for cv, cval in zip(ij.constvars, inner.consts):
            nv = const_of.get(id(cval))
            if nv is None:
                nv = Var(cv.aval)
                constvars.append(nv)
                consts.append(cval)
                const_of[id(cval)] = nv
            m[cv] = nv
        for iv, outer_atom in zip(ij.invars, eqn.invars):
            m[iv] = outer_atom
        for ie in ij.eqns:
            new_out = []
            for ov in ie.outvars:
                nv = Var(ov.aval)
                m[ov] = nv
                new_out.append(nv)
            new_in = [m.get(i, i) if isinstance(i, Var) else i
                      for i in ie.invars]
            out_eqns.append(ie.replace(invars=new_in, outvars=new_out))
        for ov_outer, ov_inner in zip(eqn.outvars, ij.outvars):
            a = ov_inner if isinstance(ov_inner, Literal) \
                else m.get(ov_inner, ov_inner)
            subst[ov_outer] = a

    new_outvars = [res(o) if isinstance(o, Var) else o
                   for o in jaxpr.outvars]
    new_jaxpr = Jaxpr(constvars=constvars, invars=jaxpr.invars,
                      outvars=new_outvars, eqns=out_eqns,
                      effects=jaxpr.effects, debug_info=jaxpr.debug_info)
    return ClosedJaxpr(new_jaxpr, consts)
