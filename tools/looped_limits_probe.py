#!/usr/bin/env python3
"""On-chip readings for the limits of ``correct`` in ``ouro-reason-decode``
(``benchmark/reference/ouro.py``, ``benchmark/kinds/serve_looped.py``), at
the published sizes. Needs a TPU.

    python3 tools/looped_limits_probe.py [--paths nocache cached stream]
        [--seeds N ...] [--light-seeds N ...]

``nocache``: the bf16 model's own forward on 256 tokens against the
reference as it is, in 8-bit floats, and with each published term taken out
(``MUTATIONS``). ``cached``: 448 tokens prefilled in the deployment's chunk
and 8 decoded through every pass's slice of a fresh paged cache
(``cached_outputs``, what checks (b) and (c) read) against the reference as
it is and with a pass reading another pass's keys and values; and the
SYSTEM broken: every pass through pass 0's slice (the table offset
dropped). ``stream``: a greedy stream of the model's own (``generate()``:
128 prompt + 192 new tokens through the cache) and a stream of random
tokens through the timed path's check (``emitted_vs_reference``), against
the reference as it is and against a three-pass reference. One JSON line a
seed. ``--light-seeds`` take only the unbroken readings (what the limits
must pass): the broken variants miss by so much that two seeds show it.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from benchmark import common, weights_by_class               # noqa: E402
from paddle_tpu.utils.compile_cache import (                  # noqa: E402
    configure_compile_cache)


def nocache(ref, model, params, c, seed, light=False):
    ids = np.random.default_rng([seed & 0xFFFFFFFF, 23]).integers(
        0, c["vocab_size"], ref.SEQ, np.int32)
    logits, p = ref.model_outputs(model, ids)
    out = {"as_is": ref.compare(logits, p, params, c, ids)}
    if light:
        return out
    out["float8"] = ref.compare(logits, p, params, c, ids,
                                matmul_dtype=jnp.float8_e4m3fn)
    for m in ref.MUTATIONS:
        out[m] = ref.compare(logits, p, params, c, ids, mutate=(m,))
    return out


def cached(ref, model, params, c, seed, light=False):
    from paddle_tpu.models import ouro
    n = int(c["deployment"]["check_context"]) + ref.DECODE
    ids = np.random.default_rng([seed & 0xFFFFFFFF, 29]).integers(
        0, c["vocab_size"], n, np.int32)
    rows, got, p = ref.cached_outputs(model, ids, chunk=256,
                                      decode=ref.DECODE)
    kw = dict(logits_at=rows)
    out = {"rows": len(rows),
           "as_is": ref.compare(got, p, params, c, ids, **kw)}
    if light:
        return out
    for m in ("kv_prev_pass", "kv_last_pass", "three_passes"):
        out[m] = ref.compare(got, p, params, c, ids, mutate=(m,), **kw)
    # the system broken: no slice a pass (every pass reads and writes
    # through the table as it is, so a pass finds the last pass's keys and
    # values for every earlier chunk and step)
    real = ouro.OuroModel._one_pass

    def no_offset(self, x, arenas, pos, table):
        blocks = arenas[0].shape[0] // self.config.total_ut_steps
        return real(self, x, arenas, pos, table % blocks)
    ouro.OuroModel._one_pass = no_offset
    try:
        rows, got, p = ref.cached_outputs(model, ids, chunk=256,
                                          decode=ref.DECODE)
    finally:
        ouro.OuroModel._one_pass = real
    out["system_no_slice_offset"] = ref.compare(got, p, params, c, ids, **kw)
    return out


def stream(ref, model, params, c, seed, light=False):
    import paddle_tpu as paddle
    latent = common.load_module("kinds", "serve_latent_moe.py")
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 31])
    prompt = rng.integers(0, c["vocab_size"], 128, np.int32)
    seq = np.asarray(model.generate(paddle.to_tensor(prompt[None]),
                                    max_new_tokens=192)._value[0])
    emitted = seq[len(prompt):]

    class Broken:
        @staticmethod
        def forward(*a, **kw):
            return ref.forward(*a, mutate=("three_passes",), **kw)
    own = latent.emitted_vs_reference(ref, params, c, prompt, emitted, print)
    if light:
        return {"own_stream_sd": own}
    return {
        "own_stream_sd": own,
        "own_stream_vs_three_passes_sd": latent.emitted_vs_reference(
            Broken, params, c, prompt, emitted, print),
        "random_stream_sd": latent.emitted_vs_reference(
            ref, params, c, prompt,
            rng.integers(0, c["vocab_size"], 192, np.int32), print)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paths", nargs="+",
                    default=["nocache", "cached", "stream"],
                    choices=["nocache", "cached", "stream"])
    ap.add_argument("--seeds", nargs="+", type=int, default=[3000000019])
    ap.add_argument("--light-seeds", nargs="*", type=int, default=[])
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("looped_limits_probe: needs a TPU (the published sizes do "
                 "not fit a CPU run)")
    configure_compile_cache(0.0)
    c = common.load_json("configs", "Ouro-2.6B.json")
    ref = common.load_module("reference", "ouro.py")
    for seed in args.seeds + args.light_seeds:
        t = time.time()
        model = weights_by_class.build_lazy(
            weights_by_class.model_config(c), seed)
        params = {k: p._value for k, p in model.named_parameters()}
        out = {"seed": seed}
        for path in args.paths:
            out[path] = {"nocache": nocache, "cached": cached,
                         "stream": stream}[path](
                ref, model, params, c, seed, seed in args.light_seeds)
        out["seconds"] = time.time() - t
        print(json.dumps(out), flush=True)
        del model, params


if __name__ == "__main__":
    main()
