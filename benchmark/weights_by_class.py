"""Random weights from ``--seed`` for a model whose CLASS the configuration
file names, made on the device through the model's own initializers.

``weights.py`` builds ``LlamaForCausalLM``; a configuration of another class
says which (``model_class`` and ``config_class``, ``"module:Name"``) and this
builds it, so the next model is data. The configuration dataclass takes
every key of the file that it has a field for (``torch_dtype`` is its
``dtype``).

``build_lazy`` is ``weights.build_lazy`` with one difference: the deferred
initializers run in one jitted program PER GROUP of parameters (a decoder
layer, the embedding, the head), and groups with the same shapes and
initializers share the compiled program — seven expert layers compile once,
and no program holds more than a layer's temporaries beside a model that
fills most of the chip. ``init_overrides`` in the configuration file
(``{"<parameter-name suffix>": {"normal_std": s}}``) redraws the named
parameters from N(0, s), for values the class initialises to a constant
that would hide a term (a selection bias of zeros never changes a pick).
"""
from __future__ import annotations

import dataclasses
import importlib
import re

import jax

from .common import fold_seed
from .weights import _default_dtype


def _named(path: str):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def model_config(c: dict, **extra):
    """The configuration file's sizes as the class's own config object."""
    cls = _named(c["config_class"])
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in c.items() if k in fields}
    kw["dtype"] = c["torch_dtype"]
    kw.update(extra)
    cfg = cls(**kw)
    cfg.model_class = c["model_class"]
    cfg.init_overrides = c.get("init_overrides", {})
    return cfg


def _group(name: str) -> str:
    m = re.match(r"^(.*?layers\.\d+)\.", name)
    return m.group(1) if m else name.split(".")[0]


def _signature(init, shape, dtype):
    return (type(init).__name__, tuple(sorted(
        (k, v) for k, v in vars(init).items()
        if isinstance(v, (int, float, str, type(None))))),
        tuple(shape), str(dtype))


def build_lazy(cfg, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu import framework
    from paddle_tpu.nn import initializer as I
    from paddle_tpu.tensor import LazyParameter
    with _default_dtype(cfg.dtype), paddle.LazyGuard():
        model = _named(cfg.model_class)(cfg)
    groups = {}
    for name, p in model.named_parameters():
        if not (isinstance(p, LazyParameter) and not p.materialized()):
            raise RuntimeError(f"{name} was initialised on the host during "
                               "the lazy build")
        init, shape, dtype = p._lazy_init
        for suffix, how in cfg.init_overrides.items():
            if name.endswith(suffix):
                init = I.Normal(0.0, float(how["normal_std"]))
        groups.setdefault(_group(name), []).append((p, init, shape, dtype))
    programs = {}
    key = jax.random.PRNGKey(fold_seed(seed))
    for g, (_, members) in enumerate(sorted(groups.items())):
        sig = tuple(_signature(*m[1:]) for m in members)
        if sig not in programs:
            specs = [m[1:] for m in members]

            def make(k, specs=specs):
                with framework.rng_context(k):
                    return [init(shape, dtype) for init, shape, dtype in specs]
            programs[sig] = jax.jit(make)
        values = programs[sig](jax.random.fold_in(key, g))
        for (p, *_), v in zip(members, values):
            p._value = v
    return model
