"""The one general traffic generator: every serving mix is a data file of
parameters (``benchmark/workloads/<cell>.json``, key ``traffic``) read here.

A mix fixes a POOL of request shapes, not a stream of draws: ``pool``
(prompt length, output length) pairs that are the evenly spaced quantiles
of the stated distributions, paired by a permutation from the mix's own
``shape_seed``. ``--seed`` only decides the ORDER the pool is walked in
(a fresh permutation each cycle) and the token ids, so every seed offers
the same set of sizes, in another order: a run-to-run difference is then
the system's, not the draw's. Arrival gaps are handled the same way (the
quantiles of the exponential distribution, permuted), so ``pool``
arrivals always span exactly ``pool / rate`` seconds.

Parameters (all under ``traffic``):
  prompt_len, output_len   {"dist": "uniform"|"bounded_pareto"|"fixed", ...}
  first_wave_output_len    optional: output lengths of the first
                           ``first_wave`` requests (a backlog cell fills its
                           slots with spread-out remaining lengths)
  arrivals                 {"process": "backlog", "depth": n}  queue kept
                           ``depth`` deep, no due times; or
                           {"process": "poisson", "rate_per_s": r,
                            "burst": {"factor": f, "on_s": a, "period_s": p}}
                           open loop on the wall clock; inside a burst the
                           clock runs ``factor`` times faster, outside it
                           slower so the mean rate stays ``rate_per_s``
  shared_prefix            optional {"share": 0..1, "len": n, "count": k}:
                           that share of requests starts with one of ``k``
                           fixed prefixes of ``n`` tokens
  pool, shape_seed         pool size and the seed that pairs the shapes
"""
from __future__ import annotations

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-point quantiles of a length distribution, as whole
    numbers inside [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if dist == "uniform":
        x = lo + u * (hi - lo)
    elif dist == "bounded_pareto":
        # inverse CDF of the Pareto law truncated to [lo, hi]
        # (serving/loadgen.py _bounded_pareto, copied)
        a = float(spec["alpha"])
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng([int(w) & 0xFFFFFFFF for w in words])


class Traffic:
    """Request ``i`` of the stream for one ``--seed``; random access, so
    the driver asks for requests as they fall due."""

    def __init__(self, params: dict, seed: int, vocab: int):
        self.seed = int(seed)
        self.vocab = int(vocab)
        n = self.pool = int(params["pool"])
        shape = _rng(params["shape_seed"], 1)
        self._prompts = quantiles(params["prompt_len"], n)[shape.permutation(n)]
        self._outputs = quantiles(params["output_len"], n)
        arr = params["arrivals"]
        self.backlog_depth = int(arr["depth"]) \
            if arr["process"] == "backlog" else None
        self.first_wave = int(params.get("first_wave", 0))
        if self.first_wave:
            self._first = quantiles(params["first_wave_output_len"],
                                    self.first_wave)[
                _rng(params["shape_seed"], 2).permutation(self.first_wave)]
        if arr["process"] == "poisson":
            self.rate = float(arr["rate_per_s"])
            u = (np.arange(n) + 0.5) / n
            self._gaps = -np.log1p(-u)
            self._gaps *= n / self._gaps.sum() / self.rate   # mean 1/rate
            self.burst = arr.get("burst")
        elif arr["process"] != "backlog":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        sp = params.get("shared_prefix")
        self._prefixes = None
        if sp:
            r = _rng(params["shape_seed"], 3)
            self._prefixes = r.integers(0, vocab, (int(sp["count"]),
                                                   int(sp["len"])), np.int32)
            self._share = float(sp["share"])
        self._order = {}          # cycle -> permutation of the pool
        self._due = []            # cumulative due offsets, grown on demand
        self._t = 0.0

    def _perm(self, cycle: int, what: int) -> np.ndarray:
        key = (cycle, what)
        if key not in self._order:
            self._order[key] = _rng(self.seed, self.seed >> 32, cycle,
                                    what).permutation(self.pool)
        return self._order[key]

    def _stretch(self, t: float, gap: float) -> float:
        """Advance the wall clock by a unit-rate ``gap`` under bursts."""
        b = self.burst
        if not b:
            return t + gap
        f, on, period = float(b["factor"]), float(b["on_s"]), float(b["period_s"])
        slow = (period - on * f) / (period - on)     # off-burst speed
        if slow <= 0:
            raise ValueError("burst carries more than the whole mean rate")
        while gap > 0:
            phase = t % period
            speed, left = (f, on - phase) if phase < on \
                else (slow, period - phase)
            step = min(gap / speed, left)
            t += step
            gap -= step * speed
        return t

    def due(self, i: int):
        """Seconds after the arrival process starts at which request ``i``
        is due; None in a backlog mix."""
        if self.backlog_depth is not None:
            return None
        while len(self._due) <= i:
            j = len(self._due)
            gap = self._gaps[self._perm(j // self.pool, 0)[j % self.pool]]
            self._t = self._stretch(self._t, float(gap))
            self._due.append(self._t)
        return self._due[i]

    def request(self, i: int) -> dict:
        k = int(self._perm(i // self.pool, 1)[i % self.pool])
        n_prompt = int(self._prompts[k])
        n_out = int(self._first[i]) if i < self.first_wave \
            else int(self._outputs[k])
        r = _rng(self.seed, self.seed >> 32, i, 7)
        prompt = r.integers(0, self.vocab, n_prompt, np.int32)
        if self._prefixes is not None and r.random() < self._share:
            pre = self._prefixes[int(r.integers(len(self._prefixes)))]
            m = min(len(pre), n_prompt - 1)
            prompt[:m] = pre[:m]
        return {"prompt": prompt, "max_new_tokens": n_out}


def describe(params: dict) -> dict:
    """Mean and tails of the pool, for the run's header line."""
    out = {}
    for k in ("prompt_len", "output_len"):
        q = quantiles(params[k], int(params["pool"]))
        out[k] = {"mean": float(q.mean()), "p50": float(np.median(q)),
                  "p95": float(np.percentile(q, 95)), "max": int(q.max())}
    return out

