"""paddle_tpu.jit — the static/compiled boundary.

Reference parity: ``paddle.jit.to_static`` (SOT bytecode capture / AST
dy2static — reference: python/paddle/jit/ — verify) and ``jit.save/load``.

TPU-native design (SURVEY §7 "hard part #1"): instead of bytecode capture we
exploit that every op dispatches through ``apply_op`` on pure jax functions,
so *running the Python forward under jax tracing IS the graph capture*
(jax tracing ≡ SOT; the jit boundary ≡ to_static). Two compiled paths:

1. ``to_static(layer_or_fn)`` — compiles forward into one XLA program;
   backward still works because the compiled program is recorded on the
   eager tape as a single fused op (jax.vjp of a pjit stays compiled).
2. ``TrainStep(model, loss_fn, optimizer)`` — the perf path: forward +
   backward + optimizer update + LR schedule fused into ONE donated,
   jitted XLA program over the (params, opt-state, batch, rng) pytree.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import framework
from ..observability.tracing import named_program, span as _span
from ..tensor import Tensor, Parameter, apply_op
from ..nn.layer import Layer

from .sot import SotFunction, symbolic_call  # noqa: E402,F401

__all__ = ["to_static", "not_to_static", "TrainStep", "EvalStep", "save",
           "SotFunction", "symbolic_call",
           "load", "ignore_module", "enable_to_static", "set_code_level"]

_TO_STATIC_ENABLED = True


def enable_to_static(flag: bool):
    global _TO_STATIC_ENABLED
    _TO_STATIC_ENABLED = bool(flag)


def set_code_level(level=100, also_to_stderr=False):
    """Parity no-op (reference: paddle.jit.set_code_level prints SOT-
    transformed code — verify): our SOT records op graphs rather than
    rewriting bytecode; inspect SotFunction.traces / sot_stats instead.
    """


def ignore_module(modules):
    pass  # parity no-op: nothing to ignore in trace-based capture


def not_to_static(fn=None):
    if fn is None:
        return lambda f: f
    return fn


def _collect_layers(obj) -> list[Layer]:
    """Find Layers reachable from a callable: bound self, closure cells."""
    layers = []
    if isinstance(obj, Layer):
        return [obj]
    self_obj = getattr(obj, "__self__", None)
    if isinstance(self_obj, Layer):
        layers.append(self_obj)
    clo = getattr(obj, "__closure__", None)
    if clo:
        for cell in clo:
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            if isinstance(v, Layer):
                layers.append(v)
    return layers


class _PassesJit:
    """jit-equivalent wrapper that traces the pure step function, runs
    the jaxpr pass pipeline on it, and compiles the TRANSFORMED program
    — so what XLA sees is the post-fusion jaxpr, not the traced one.
    One (shapes, dtypes) signature -> one transformed executable;
    ``pass_stats`` holds the last trace's before/after program_stats and
    the PassManager's per-pass eqn counts."""

    _trace_seq = 0          # class-wide: orders traces across instances

    def __init__(self, pure: Callable, passes):
        self._pure = pure
        self._passes = list(passes)
        self._compiled: dict = {}
        self.pass_stats = None

    def __call__(self, *flat):
        key = tuple((tuple(jnp.shape(v)), str(jnp.result_type(v)))
                    for v in flat)
        entry = self._compiled.get(key)
        if entry is None:
            from ..passes import PassManager, program_stats
            closed = jax.make_jaxpr(self._pure)(*flat)
            pm = PassManager(self._passes)
            before = program_stats(closed)
            closed = pm.run(closed)
            _PassesJit._trace_seq += 1
            self.pass_stats = {"before": before,
                               "after": program_stats(closed),
                               "per_pass": pm.last_stats,
                               "trace_seq": _PassesJit._trace_seq}

            def run_transformed(*args, _c=closed):
                return tuple(jax.core.eval_jaxpr(_c.jaxpr, _c.consts,
                                                 *args))
            entry = jax.jit(run_transformed)
            self._compiled[key] = entry
        return entry(*flat)


class StaticFunction:
    """Callable that runs `fn` as one compiled XLA program."""

    def __init__(self, fn: Callable, layers: Optional[list] = None,
                 input_spec=None, backend=None, passes=None, **kwargs):
        self._fn = fn
        self._layers = layers if layers is not None else _collect_layers(fn)
        self._input_spec = input_spec
        self._passes = list(passes) if passes else None
        self._cache: dict = {}
        functools.update_wrapper(self, fn, updated=[])

    @property
    def pass_stats(self):
        """Before/after program stats of the most recent passes trace
        (None until the first compiled call, or without passes=)."""
        latest = None
        for entry in self._cache.values():
            if isinstance(entry, tuple) and isinstance(entry[0],
                                                       _PassesJit):
                s = entry[0].pass_stats
                if s is not None and (latest is None
                                      or s["trace_seq"]
                                      > latest["trace_seq"]):
                    latest = s
        return latest

    # paddle API surface
    @property
    def forward(self):
        return self

    def concrete_program_specify_input_spec(self, *a, **k):
        return None

    def _state(self):
        ptensors, pnames = [], []
        btensors, bnames = [], []
        seen = set()
        for layer in self._layers:
            for n, p in layer.named_parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    pnames.append(n)
                    ptensors.append(p)
            for n, b in layer.named_buffers():
                if id(b) not in seen:
                    seen.add(id(b))
                    bnames.append(n)
                    btensors.append(b)
        return ptensors, btensors

    def _build(self, n_inputs: int, static_key):
        ptensors, btensors = self._state()
        np_, nb = len(ptensors), len(btensors)
        holder = {"tree": None, "n_out": None}
        arg_template = static_key[0]  # tuple marking Tensor positions
        kwargs = dict(static_key[1])

        def pure(*flat):
            key = flat[0]
            pv = flat[1:1 + np_]
            bv = flat[1 + np_:1 + np_ + nb]
            iv = flat[1 + np_ + nb:]
            saved = [(t, t._value) for t in ptensors + btensors]
            try:
                for t, v in zip(ptensors, pv):
                    t._value = v
                for t, v in zip(btensors, bv):
                    t._value = v
                args = []
                it = iter(iv)
                for is_tensor, static_val in arg_template:
                    if is_tensor:
                        args.append(Tensor(next(it)))
                    else:
                        args.append(static_val)
                with framework.functional_mode(), framework.rng_context(key):
                    out = self._fn(*args, **kwargs)
                leaves, tree = jax.tree.flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                out_vals = [l._value if isinstance(l, Tensor) else l
                            for l in leaves]
                holder["tree"] = tree
                holder["n_out"] = len(out_vals)
                new_bufs = [t._value for t in btensors]
                return tuple(out_vals) + tuple(new_bufs)
            finally:
                for t, v in saved:
                    t._value = v

        if self._passes:
            return _PassesJit(pure, self._passes), holder
        return jax.jit(pure), holder

    def _try_dy2static(self, static_key):
        """AST-convert tensor control flow; on success, register the
        converted runner for this signature. The conversion itself is
        signature-independent, so it runs ONCE and later signatures reuse
        the same converted StaticFunction."""
        from . import dy2static
        if getattr(self, "_dy2static_run", None) is not None:
            self._cache[static_key] = ("dy2static", self._dy2static_run)
            return self._dy2static_run
        if getattr(self, "_dy2static_attempted", False):
            return None
        self._dy2static_attempted = True
        new_fn = dy2static.convert_function(self._fn)
        if new_fn is None:
            return None
        sub = StaticFunction(new_fn, layers=self._layers,
                             passes=self._passes)
        self._dy2static_sub = sub   # introspection (tests/debugging)

        def run(*a, **k):
            sig = self._sig_key(a, k)
            try:
                return sub(*a, **k)
            except dy2static.ConversionError as ce:
                split = self._try_graph_break(sig)
                if split is not None:
                    return split(*a, **k)
                import warnings
                warnings.warn(
                    f"to_static: dy2static conversion not lowerable "
                    f"({ce}); falling back to eager for this signature",
                    stacklevel=2)
                self._cache[sig] = "eager"
                return self._fn(*a, **k)
            except ValueError as ve:
                if "Reverse-mode differentiation" not in str(ve):
                    raise
                # a converted lax.while_loop cannot be transposed (XLA
                # has no reverse-mode for dynamic trip counts); under
                # grad, degrade to the eager Python loop, which unrolls
                # per concrete values and differentiates fine
                import warnings
                warnings.warn(
                    "to_static: converted while-loop is not reverse-"
                    "differentiable (dynamic trip count); falling back "
                    "to eager for this signature", stacklevel=2)
                self._cache[sig] = "eager"
                return self._fn(*a, **k)
        self._dy2static_run = run
        self._cache[static_key] = ("dy2static", run)
        return run

    def _try_graph_break(self, static_key):
        """SOT-analogue stage (reference: python/paddle/jit/sot/ —
        verify): split the function at breaking statements and compile
        the spans between them, instead of running the WHOLE function
        eagerly. Conversion runs once; later signatures reuse it."""
        from . import graph_break
        if getattr(self, "_graph_break_run", None) is not None:
            self._cache[static_key] = ("dy2static", self._graph_break_run)
            return self._graph_break_run
        if getattr(self, "_graph_break_attempted", False):
            return None
        self._graph_break_attempted = True
        split = graph_break.split_function(self._fn, layers=self._layers)
        if split is None:
            return None
        import warnings
        warnings.warn(
            f"to_static: {getattr(self._fn, '__name__', '?')} contains "
            f"host-materializing statements; compiled with "
            f"{len(split._jst_spans)} subgraph span(s) and eager graph "
            f"breaks between them (SOT-analogue)", stacklevel=2)
        self._graph_break_run = split
        self._cache[static_key] = ("dy2static", split)
        return split

    @staticmethod
    def _sig_key(args, kwargs):
        arg_template = tuple(
            (True, None) if isinstance(a, Tensor) else (False, a)
            for a in args)
        return (arg_template,
                tuple(sorted(kwargs.items())) if kwargs else ())

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED:
            return self._fn(*args, **kwargs)
        ptensors, btensors = self._state()
        static_key = self._sig_key(args, kwargs)
        inputs = [a for a in args if isinstance(a, Tensor)]
        try:
            entry = self._cache.get(static_key)
        except TypeError:
            # an unhashable non-Tensor arg (list/dict) cannot key the
            # program cache — run this call eagerly rather than crash
            return self._fn(*args, **kwargs)
        if entry == "eager":
            return self._fn(*args, **kwargs)
        if isinstance(entry, tuple) and entry and entry[0] == "dy2static":
            return entry[1](*args, **kwargs)
        if entry is None:
            if getattr(self, "_dy2static_run", None) is not None:
                # the function provably contains tensor control flow;
                # re-tracing the original would just re-raise — reuse the
                # converted runner for this new signature directly
                run = self._dy2static_run
                self._cache[static_key] = ("dy2static", run)
                return run(*args, **kwargs)
            if getattr(self, "_graph_break_run", None) is not None:
                # same for an already-split function: a new signature
                # must not re-pay the failed whole-function trace
                run = self._graph_break_run
                self._cache[static_key] = ("dy2static", run)
                return run(*args, **kwargs)
            entry = self._build(len(inputs), static_key)
            self._cache[static_key] = entry
        jitted, holder = entry

        key = framework.split_key()
        key_t = Tensor(key)  # ride through apply_op as a non-diff input
        flat_args = [key_t] + ptensors + btensors + inputs
        wants_grad = framework.is_grad_enabled() and any(
            not t.stop_gradient for t in flat_args)
        try:
            with framework.functional_grad_hint(wants_grad):
                out = apply_op(jitted, *flat_args)
        except (jax.errors.TracerBoolConversionError,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError) as e:
            # data-dependent Python control flow leaked a tracer. Before
            # giving up, try the dy2static AST conversion (reference:
            # python/paddle/jit/dy2static/ IfElse/Loop transformers):
            # tensor `if`/`while` become lax.cond / lax.while_loop and
            # the signature stays fully compiled
            converted = self._try_dy2static(static_key)
            if converted is not None:
                return converted(*args, **kwargs)
            # SOT-analogue graph breaks: keep compiled spans, run only
            # the breaking statements in Python (reference:
            # python/paddle/jit/sot/ opcode-level breaks — verify)
            split = self._try_graph_break(static_key)
            if split is not None:
                return split(*args, **kwargs)
            import warnings
            first_line = str(e).splitlines()[0] if str(e) else repr(e)
            warnings.warn(
                "to_static: forward has data-dependent Python control "
                f"flow ({first_line}); falling back to EAGER execution "
                "for this input signature (no compilable span found). "
                "Rewrite with lax.cond/where for a fully compiled step.",
                stacklevel=2)
            self._cache[static_key] = "eager"
            return self._fn(*args, **kwargs)
        outs = list(out) if isinstance(out, (tuple, list)) else [out]
        n_out = holder["n_out"]
        out_leaves = outs[:n_out]
        new_bufs = outs[n_out:]
        for t, nb_ in zip(btensors, new_bufs):
            t._update_value(nb_._value)
        result = jax.tree.unflatten(holder["tree"], out_leaves)
        return result


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, passes=None, **kwargs):
    """Decorator/wrapper compiling a Layer or function into one XLA
    program. ``full_graph=True`` (default) is whole-graph jax tracing;
    ``full_graph=False`` routes through the bytecode-level SOT executor
    (reference: to_static's SOT default with graph breaks —
    python/paddle/jit/api.py — verify): Python control flow over tensor
    DATA is allowed and splits the program at graph breaks instead of
    raising a tracer error.

    ``passes``: optional sequence of jaxpr passes (see
    ``paddle_tpu.passes.default_pipeline``) run on the traced program
    before compilation — the TRANSFORMED jaxpr is what jit compiles
    (reference: build_strategy.build_cinn_pass / the PIR PassManager
    hook on to_static — verify). Inspect ``.pass_stats`` on the result
    for before/after equation counts. Passes apply to fully-compiled
    signatures (including dy2static-converted ones); graph-break spans
    and eager fallbacks run untransformed. Incompatible with
    ``full_graph=False`` (the SOT executor has no whole-program jaxpr
    to transform) — that combination raises rather than silently
    dropping the pipeline."""
    def decorate(obj):
        if not full_graph:
            if passes:
                raise ValueError(
                    "to_static(passes=...) requires full_graph=True: "
                    "the SOT executor compiles opcode-level spans, not "
                    "one whole-program jaxpr the pass pipeline could "
                    "transform")
            if isinstance(obj, Layer):
                obj.forward = SotFunction(obj.forward)
                return obj
            return SotFunction(obj)
        if isinstance(obj, Layer):
            static = StaticFunction(obj.forward, layers=[obj],
                                    input_spec=input_spec, passes=passes)
            obj.forward = static
            return obj
        return StaticFunction(obj, input_spec=input_spec, passes=passes)
    if function is not None:
        return decorate(function)
    return decorate


# ---------------------------------------------------------------------------
# TrainStep: fused fwd+bwd+opt — the perf path
# ---------------------------------------------------------------------------

# The train step's name as a device trace shows it ("XLA Modules" line):
# benchmark/kinds/train.py reads device time by it, so it is set on
# purpose where the function is built (``tracing.named_program``) and
# pinned in tests/test_trace_names.py.
TRAIN_STEP_PROGRAM = "jit_step"


class TrainStep:
    """Compile model+loss+optimizer into one donated XLA train step.

    Reference analog: the whole dygraph loop (forward, backward, Reducer,
    opt.step) — here a single ``jax.jit`` with buffer donation so parameter
    and optimizer-state memory is reused in place.

        step = TrainStep(model, loss_fn, opt)
        loss = step(x, y)          # one fused XLA program per call
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate: bool = True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._jitted = None
        self._donate = donate
        self._pnames = None
        self._compiled_info = None
        self._steps = 0                 # calls so far: the span's ``step``

    def _build(self):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        # key trainable params by the OPTIMIZER's unique names so opt-state
        # slots and grads line up inside the functional update
        opt_name_of = {id(p): n for n, p in
                       zip(opt._param_names, opt._param_list)}
        ptensors, frozen = {}, {}
        for n, p in model.named_parameters():
            if not p.stop_gradient and id(p) in opt_name_of:
                ptensors[opt_name_of[id(p)]] = p
            else:
                frozen[n] = p
        btensors = dict(model.named_buffers())
        self._pnames = list(ptensors)

        def run_forward(pvals, bvals, fvals, key, batch):
            saved = [(t, t._value) for t in
                     list(ptensors.values()) + list(btensors.values()) +
                     list(frozen.values())]
            try:
                for n, v in pvals.items():
                    ptensors[n]._value = v
                for n, v in bvals.items():
                    btensors[n]._value = v
                for n, v in fvals.items():
                    frozen[n]._value = v
                with framework.functional_mode(), framework.rng_context(key):
                    batch_t = jax.tree.map(Tensor, batch)
                    out = loss_fn(model, batch_t)
                    loss = out[0] if isinstance(out, tuple) else out
                    aux = out[1:] if isinstance(out, tuple) else ()
                new_bufs = {n: t._value for n, t in btensors.items()}
                aux_vals = jax.tree.map(
                    lambda x: x._value if isinstance(x, Tensor) else x, aux)
                return loss._value, (new_bufs, aux_vals)
            finally:
                for t, v in saved:
                    t._value = v

        def step(pvals, opt_state, bvals, fvals, key, lr_value, batch):
            (loss, (new_bufs, aux)), grads = jax.value_and_grad(
                run_forward, has_aux=True)(pvals, bvals, fvals, key, batch)
            new_params, new_opt_state = opt.functional_update(
                pvals, grads, opt_state, lr_value)
            return loss, new_params, new_opt_state, new_bufs, aux

        donate = (0, 1) if self._donate else ()
        self._step_fn = step            # uncompiled core (run_steps scans it)
        self._jitted = jax.jit(named_program(step, TRAIN_STEP_PROGRAM),
                               donate_argnums=donate)
        self._ptensors, self._btensors, self._frozen = \
            ptensors, btensors, frozen

    def _step_args(self, batch):
        pvals = {n: t._value for n, t in self._ptensors.items()}
        bvals = {n: t._value for n, t in self._btensors.items()}
        fvals = {n: t._value for n, t in self._frozen.items()}
        opt_state = self.optimizer.functional_state()
        key = framework.split_key()
        lr_value = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        batch_vals = jax.tree.map(
            lambda x: x._value if isinstance(x, Tensor)
            else x if isinstance(x, jax.ShapeDtypeStruct)  # AOT specs
            else jnp.asarray(x),
            batch, is_leaf=lambda x: isinstance(x, Tensor))
        return pvals, opt_state, bvals, fvals, key, lr_value, batch_vals

    def lower(self, batch):
        """AOT path: ``jax.jit(...).lower`` of the full fused train step —
        compile-time cost/memory introspection without running it
        (``.compile().cost_analysis()``, ``.memory_analysis()``)."""
        if self._jitted is None:
            self._build()
        return self._jitted.lower(*self._step_args(batch))

    def __call__(self, batch):
        """batch: pytree of Tensors/arrays. Returns loss Tensor (+aux)."""
        if self._jitted is None:
            self._build()
        # flattening the arguments and the enqueue; the device's time is
        # the caller's (whoever fetches the loss waits for it)
        with _span("train.step_dispatch", step=self._steps):
            loss, new_params, new_opt_state, new_bufs, aux = self._jitted(
                *self._step_args(batch))
        self._steps += 1
        for n, v in new_params.items():
            self._ptensors[n]._update_value(v)
        for n, v in new_bufs.items():
            self._btensors[n]._update_value(v)
        self.optimizer.load_functional_state(new_opt_state)
        if aux:
            return (Tensor(loss),) + tuple(
                jax.tree.map(Tensor, a) for a in aux)
        return Tensor(loss)

    def _build_multi(self, n_steps):
        """One XLA program running ``n_steps`` train steps as lax.scan —
        no host round-trip between steps (the per-step dispatch gap
        shows up as device IDLE; PROFILE_r03.json measured 9.3%). Same state threading/donation as the single
        step; the per-step rng keys are split on device; LR is read once
        per dispatch (a per-step LR schedule advances per CALL, not per
        inner step — use single-step mode when that distinction
        matters)."""
        if self._jitted is None:
            self._build()

        def multi(pvals, opt_state, bvals, fvals, key, lr_value, batch):
            def body(carry, k):
                pv, os_, bv = carry
                loss, pv, os_, bv, aux = self._step_fn(
                    pv, os_, bv, fvals, k, lr_value, batch)
                return (pv, os_, bv), (loss, aux)
            keys = jax.random.split(key, n_steps)
            (pv, os_, bv), (losses, auxes) = jax.lax.scan(
                body, (pvals, opt_state, bvals), keys)
            last_aux = jax.tree.map(lambda a: a[-1], auxes)
            return losses[-1], pv, os_, bv, last_aux

        donate = (0, 1) if self._donate else ()
        return jax.jit(multi, donate_argnums=donate)

    def run_steps(self, batch, n_steps):
        """Run ``n_steps`` optimizer steps on ``batch`` in ONE compiled
        dispatch; returns the last step's loss. Parity with n_steps
        sequential __call__ invocations (modulo the rng key sequence and
        per-step LR schedules; see _build_multi)."""
        if n_steps == 1:
            return self(batch)
        cache = getattr(self, "_multi_cache", None)
        if cache is None:
            cache = self._multi_cache = {}
        if n_steps not in cache:
            cache[n_steps] = self._build_multi(n_steps)
        loss, new_params, new_opt_state, new_bufs, aux = cache[n_steps](
            *self._step_args(batch))
        for n, v in new_params.items():
            self._ptensors[n]._update_value(v)
        for n, v in new_bufs.items():
            self._btensors[n]._update_value(v)
        self.optimizer.load_functional_state(new_opt_state)
        if aux:
            # last inner step's aux — same tuple shape as __call__
            return (Tensor(loss),) + tuple(
                jax.tree.map(Tensor, a) for a in aux)
        return Tensor(loss)


class EvalStep:
    """Compiled inference step: (batch) -> outputs, params frozen."""

    def __init__(self, model: Layer, fn: Optional[Callable] = None):
        self.model = model
        self.fn = fn or (lambda m, b: m(b))
        self._jitted = None

    def _build(self):
        model, fn = self.model, self.fn
        ptensors = dict(model.named_parameters())
        btensors = dict(model.named_buffers())
        self._ptensors, self._btensors = ptensors, btensors

        def run(pvals, bvals, key, batch):
            saved = [(t, t._value) for t in
                     list(ptensors.values()) + list(btensors.values())]
            try:
                for n, v in pvals.items():
                    ptensors[n]._value = v
                for n, v in bvals.items():
                    btensors[n]._value = v
                was_training = model.training
                model.eval()
                with framework.functional_mode(), framework.rng_context(key):
                    batch_t = jax.tree.map(Tensor, batch)
                    out = fn(model, batch_t)
                if was_training:
                    model.train()
                return jax.tree.map(
                    lambda x: x._value if isinstance(x, Tensor) else x, out,
                    is_leaf=lambda x: isinstance(x, Tensor))
            finally:
                for t, v in saved:
                    t._value = v

        self._jitted = jax.jit(run)

    def __call__(self, batch):
        if self._jitted is None:
            self._build()
        pvals = {n: t._value for n, t in self._ptensors.items()}
        bvals = {n: t._value for n, t in self._btensors.items()}
        key = framework.split_key()
        batch_vals = jax.tree.map(
            lambda x: x._value if isinstance(x, Tensor)
            else x if isinstance(x, jax.ShapeDtypeStruct)  # AOT specs
            else jnp.asarray(x),
            batch, is_leaf=lambda x: isinstance(x, Tensor))
        out = self._jitted(pvals, bvals, key, batch_vals)
        return jax.tree.map(Tensor, out)


# ---------------------------------------------------------------------------
# jit.save / jit.load (reference: python/paddle/jit/api.py — verify)
# ---------------------------------------------------------------------------

def save(layer, path, input_spec=None, **configs):
    """Serialize a Layer for inference (reference: paddle.jit.save
    writing program + params — verify).

    Always writes ``path.pdparams`` (state_dict + class coordinates).
    With ``input_spec``, ALSO AOT-exports the traced forward as
    serialized StableHLO (``path.pdmodel``) — then ``jit.load`` returns
    a TranslatedLayer that runs the compiled program without needing the
    model class at all (the reference's program-based load)."""
    from ..serialization import save as _save
    state = layer.state_dict() if isinstance(layer, Layer) else {}
    _save({"state": state,
           "class_module": type(layer).__module__,
           "class_name": type(layer).__name__},
          path + ".pdparams")
    if input_spec is not None:
        from ..inference import export_model
        export_model(layer, input_spec, path)


class TranslatedLayer(Layer):
    """jit.load result for a program-exported model (reference:
    TranslatedLayer — verify): a Layer whose forward executes the saved
    StableHLO program; parameters are frozen inside the artifact."""

    def __init__(self, predictor, state):
        super().__init__()
        object.__setattr__(self, "_predictor", predictor)
        object.__setattr__(self, "_saved_state", state)

    def state_dict(self, *a, **k):
        return dict(self._saved_state)

    def forward(self, *inputs):
        import numpy as np
        arrs = [i._value if isinstance(i, Tensor) else np.asarray(i)
                for i in inputs]
        outs = self._predictor.run_on_device(arrs)  # no host round trip
        outs = [Tensor(o) for o in outs]
        return outs[0] if len(outs) == 1 else tuple(outs)


def load(path, **configs):
    """Load a layer saved by jit.save. Resolution order:

    1. ``path.pdmodel`` exists (saved with input_spec) → TranslatedLayer
       running the exported StableHLO program — no model class needed.
    2. Otherwise the saved class is imported and reconstructed (must be
       constructible with no arguments) and the state_dict restored.
    3. Anything else raises with the available options — never a silent
       fallback to a bare state dict.
    """
    import os
    from ..serialization import load as _load
    blob = _load(path + ".pdparams")
    if os.path.exists(path + ".pdmodel"):
        from ..inference import Config, Predictor
        return TranslatedLayer(Predictor(Config(path)), blob["state"])
    import importlib
    try:
        mod = importlib.import_module(blob["class_module"])
        cls = getattr(mod, blob["class_name"])
        layer = cls()
    except Exception as e:
        raise RuntimeError(
            f"jit.load({path!r}): no exported program "
            f"('{path}.pdmodel') and the saved class "
            f"{blob['class_module']}.{blob['class_name']} could not be "
            f"reconstructed without arguments ({type(e).__name__}: {e}). "
            "Either re-save with input_spec= (exports a runnable "
            "program), or rebuild the model yourself and call "
            "set_state_dict(paddle.load(path + '.pdparams')['state']).")
    layer.set_state_dict(blob["state"])
    return layer
