"""Parameters that hold a FIXED SET of values, in an order from ``--seed``.

``init_quantiles`` in a configuration file (``{"<parameter-name suffix>":
{"normal_std": s}}``) gives every parameter whose name ends so the evenly
spaced quantiles of N(0, s), one a value, in an order that ``--seed`` and
the parameter's place in the model decide. It is for a parameter the class
initialises to a constant that would hide a term, as ``init_overrides``
is (``weights_by_class.py``), where a draw would also move how much work a
step does: a selection bias of 64 values decides how many experts a decode
step reads, and 64 fresh draws move that by the seed (0.933-0.956 of the
experts hit a layer a step over six seeds at N(0, 0.05) in
``lfm2-tool-agent-decode`` on a TPU v5e). ``traffic/general.py`` treats
request shapes the same way: every seed holds the same values, in another
order, so a run-to-run difference is the system's, not the draw's.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantiles(n: int, std: float) -> np.ndarray:
    """The ``n`` mid-point quantiles of N(0, std), ascending."""
    law = NormalDist(0.0, std)
    return np.array([law.inv_cdf((k + 0.5) / n) for k in range(n)],
                    np.float64)


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng([int(w) & 0xFFFFFFFF for w in words])


def apply(model, config: dict, seed: int) -> list:
    """Give the parameters ``config["init_quantiles"]`` names their values,
    in place on the device. Returns the names set (none without the key)."""
    import jax.numpy as jnp
    spec = config.get("init_quantiles") or {}
    done = []
    for i, (name, p) in enumerate(model.named_parameters()):
        how = next((h for suffix, h in spec.items()
                    if name.endswith(suffix)), None)
        if how is None:
            continue
        shape, dtype = p._value.shape, p._value.dtype
        values = quantiles(int(np.prod(shape)), float(how["normal_std"]))
        order = _rng(seed, int(seed) >> 32, i, 17).permutation(len(values))
        p._value = jnp.asarray(values[order].reshape(shape), dtype)
        done.append(name)
    return done
