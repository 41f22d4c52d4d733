"""Global framework state: grad mode, default dtype, places, RNG.

Reference parity: paddle's dygraph tracer state + ``paddle.seed`` +
``paddle.set_default_dtype`` (reference: python/paddle/base/framework.py,
python/paddle/base/core.py — verify). TPU-native design: instead of a C++
Tracer we keep a tiny amount of host state; randomness is a JAX PRNG key that
is *threaded* through jitted step functions (see ``rng_context``) so that
compiled training steps stay pure while eager code keeps Paddle's stateful
``paddle.seed`` UX.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "set_default_dtype", "get_default_dtype", "set_printoptions",
    "seed", "get_rng_key",
    "split_key", "rng_context", "no_grad_guard", "is_grad_enabled",
    "set_grad_enabled", "in_functional_mode", "functional_mode",
    "Place", "CPUPlace", "TPUPlace", "set_device", "get_device",
    "convert_dtype", "DTYPE_MAP",
]

# ---------------------------------------------------------------------------
# dtype handling
# ---------------------------------------------------------------------------

DTYPE_MAP = {
    "float32": jnp.float32, "float16": jnp.float16, "bfloat16": jnp.bfloat16,
    "float64": jnp.float32,  # x64 is disabled JAX-side; degrade to f32
    "int64": jnp.int32,      # ditto: degrade to i32 (documented divergence)
    "int32": jnp.int32, "int16": jnp.int16, "int8": jnp.int8,
    "uint8": jnp.uint8, "bool": jnp.bool_,
    "complex64": jnp.complex64,
    "fp32": jnp.float32, "fp16": jnp.float16, "bf16": jnp.bfloat16,
}


def convert_dtype(dtype: Any):
    """Normalize a paddle-style dtype spec to a jnp dtype (or None)."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype not in DTYPE_MAP:
            raise ValueError(f"unsupported dtype string: {dtype!r}")
        return DTYPE_MAP[dtype]
    if dtype in (float,):
        return _state.default_dtype
    if dtype in (int,):
        return jnp.int32
    if dtype in (bool,):
        return jnp.bool_
    d = jnp.dtype(dtype)
    # degrade 64-bit requests (jax x64 disabled; TPU-first)
    if d == jnp.dtype("float64"):
        return jnp.float32
    if d == jnp.dtype("int64"):
        return jnp.int32
    return d


# ---------------------------------------------------------------------------
# thread-local framework state
# ---------------------------------------------------------------------------

class _State(threading.local):
    def __init__(self):
        self.grad_enabled: bool = True
        self.default_dtype = jnp.float32
        self._rng_key = None           # lazy: creating a key inits a backend
        self.rng_seed: int = 0
        self.rng_stack: list = []      # functional-mode threaded keys
        self.functional: bool = False  # True while compiling a pure step
        self._device: Optional[str] = None  # lazy: don't touch devices at
        self.amp_stack: list = []      # import (one process per chip)
        self.lazy_init: int = 0        # LazyGuard nesting depth

    @property
    def rng_key(self):
        if self._rng_key is None:
            self._rng_key = jax.random.PRNGKey(self.rng_seed)
        return self._rng_key

    @rng_key.setter
    def rng_key(self, v):
        self._rng_key = v

    @property
    def device(self) -> str:
        if self._device is None:
            self._device = "tpu" if any(
                d.platform != "cpu" for d in jax.devices()) else "cpu"
        return self._device

    @device.setter
    def device(self, v: str):
        self._device = v


_state = _State()


def state() -> _State:
    return _state


def set_default_dtype(d) -> None:
    _state.default_dtype = convert_dtype(d)


def get_default_dtype() -> str:
    return jnp.dtype(_state.default_dtype).name


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr formatting (reference: paddle.set_printoptions,
    python/paddle/tensor/to_string.py — verify). Tensor.__repr__ renders
    through numpy, so this maps onto numpy's printoptions; ``sci_mode``
    toggles scientific notation (numpy's ``suppress`` inverted)."""
    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


# ---------------------------------------------------------------------------
# grad mode
# ---------------------------------------------------------------------------

def is_grad_enabled() -> bool:
    return _state.grad_enabled and not _state.functional


def set_grad_enabled(v: bool) -> None:
    _state.grad_enabled = bool(v)


@contextlib.contextmanager
def no_grad_guard():
    prev = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = prev


def in_functional_mode() -> bool:
    return _state.functional


def in_static_mode() -> bool:
    return getattr(_state, "static_mode", False)


def set_static_mode(on: bool) -> None:
    _state.static_mode = on


@contextlib.contextmanager
def functional_mode():
    """While active, ops never record onto the eager tape (the surrounding
    ``jax.grad``/``jax.vjp`` of the step compiler owns differentiation)."""
    prev = _state.functional
    _state.functional = True
    try:
        yield
    finally:
        _state.functional = prev


def functional_wants_grad() -> bool:
    """True when the functional trace in progress will be differentiated
    by its surrounding vjp (set by the step compiler; consulted by
    dy2static to refuse non-transposable control flow upfront)."""
    return getattr(_state, "functional_wants_grad", False)


@contextlib.contextmanager
def functional_grad_hint(wants: bool):
    prev = getattr(_state, "functional_wants_grad", False)
    _state.functional_wants_grad = bool(wants)
    try:
        yield
    finally:
        _state.functional_wants_grad = prev


# ---------------------------------------------------------------------------
# RNG: stateful eager seed + pure threaded keys under jit
# ---------------------------------------------------------------------------

def seed(n: int) -> None:
    _state.rng_key = jax.random.PRNGKey(int(n))


def get_rng_key():
    return _state.rng_key


def split_key():
    """One fresh PRNG subkey.

    Eager: split the global key (stateful, matches ``paddle.seed`` UX).
    Functional mode (inside a compiled step): split the *threaded* key, so
    the trace derives all randomness from the per-step input key.
    """
    if _state.rng_stack:
        key = _state.rng_stack[-1]
        key, sub = jax.random.split(key)
        _state.rng_stack[-1] = key
        return sub
    key, sub = jax.random.split(_state.rng_key)
    _state.rng_key = key
    return sub


@contextlib.contextmanager
def rng_context(key):
    """Thread `key` as the RNG source (used by the step compiler)."""
    _state.rng_stack.append(key)
    try:
        yield
    finally:
        _state.rng_stack.pop()


# ---------------------------------------------------------------------------
# places / devices
# ---------------------------------------------------------------------------

class LazyGuard:
    """Defer parameter initialization inside the context (reference:
    paddle.LazyGuard — python/paddle/fluid/lazy_init.py, verify):
    ``with paddle.LazyGuard(): model = BigModel()`` builds the full
    module tree with :class:`~paddle_tpu.tensor.LazyParameter` leaves —
    shapes/dtypes known, zero initializer compute and weight memory —
    and every parameter materializes transparently on first value
    access (forward, state_dict, optimizer)."""

    def __enter__(self):
        _state.lazy_init += 1
        return self

    def __exit__(self, *exc):
        _state.lazy_init -= 1
        return False


def in_lazy_init() -> bool:
    return _state.lazy_init > 0


class Place:
    """Device place façade (reference: phi::Place — verify). On TPU the
    runtime places data via jax default device / shardings; Place is kept for
    API parity and host/device distinction."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self.kind == other.kind
                and self.index == other.index)


def CPUPlace() -> Place:
    return Place("cpu")


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


def set_device(dev: str) -> Place:
    kind, _, idx = dev.partition(":")
    if kind in ("gpu", "cuda", "xpu"):  # parity alias: paddle scripts say gpu
        kind = "tpu"
    _state.device = kind
    return Place(kind, int(idx) if idx else 0)


def get_device() -> str:
    return _state.device


def default_backend_devices():
    return jax.devices()


class _DtypeInfo:
    def __init__(self, info, kind):
        self._i = info
        self.bits = info.bits
        self.max = float(info.max) if kind == "f" else int(info.max)
        self.min = float(info.min) if kind == "f" else int(info.min)
        self.dtype = str(np.dtype(info.dtype).name) if hasattr(
            info, "dtype") else ""
        if kind == "f":
            self.eps = float(info.eps)
            self.tiny = float(info.tiny)
            self.smallest_normal = float(info.tiny)
            self.resolution = float(info.resolution)

    def __repr__(self):
        return repr(self._i)


def iinfo(dtype):
    """paddle.iinfo parity: integer dtype limits."""
    return _DtypeInfo(np.iinfo(np.dtype(convert_dtype(dtype))), "i")


def finfo(dtype):
    """paddle.finfo parity: float dtype limits (bf16 via ml_dtypes)."""
    dt = convert_dtype(dtype)
    try:
        info = np.finfo(np.dtype(dt))
    except (TypeError, ValueError):
        import ml_dtypes
        info = ml_dtypes.finfo(dt)
    return _DtypeInfo(info, "f")


# paddle.framework.random parity (reference: python/paddle/framework/
# random.py — verify): rng state get/set over the jax key machinery
def get_rng_state():
    return [state().rng_key]


def set_rng_state(st):
    state().rng_key = st[0] if isinstance(st, (list, tuple)) else st
