#!/usr/bin/env python3
"""Where a prefill chunk's device time goes, by named scope: a traced run
of a serve cell (``benchmark/run.py --trace 1`` as it is) that also splits
the traced executions of the chunk program ``jit_chunk_fn`` over the
models' scopes (``benchmark/trace_scopes.py`` on the chunk program's
compiled text) and sums the expert layer's own sites: the prefill kernel
``moe_grouped_experts_prefill`` and XLA's ``ragged-dot``
(``benchmark/trace_kernels.py``). An experiment beside the benchmark:
no cell runs it. Needs a TPU.

    python3 tools/chunk_scope_split.py --workload <cell> --seed <n>

Before the cell's result line it prints one line ``chunk split {...}``:
the chunks traced and their device seconds, each scope's seconds and
share, the rest outside every scope, each kernel prefix's events and
seconds, the prefill kernel's events over (chunks x expert layers) (its
engagement: 1.0 where every chunk's every expert layer ran it), and the
share of 819 GB/s the prefill kernel's sites read the hit experts' bytes
at (hits a chunk: the engine's ``prefill_moe_expert_hits`` over
``prefill_chunks`` since it was built; an expert's bytes from the cell's
configuration).
"""
import json
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402

from benchmark import trace_kernels, trace_reduce, trace_scopes  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import DroplessMoE  # noqa
from paddle_tpu.serving import engine as _engine              # noqa: E402
from paddle_tpu.serving import paging                         # noqa: E402

SCOPES = ("attn", "short_conv", "mlp", "moe_router", "moe_experts",
          "moe_shared", "lm_head", "sample")
KERNELS = ("moe_grouped_experts_prefill", "ragged-dot")
HBM = 819e9
seen = {}


def spec(a):
    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding,
                                    weak_type=a.aval.weak_type)
    return a


_prefill_chunk = paging.PagedModelStepBackend.prefill_chunk


def prefill_chunk(self, *args):
    # the chunk program's operands as the engine passes them (the cache is
    # donated: kept as shapes), to compile the same program again
    seen["backend"], seen["args"] = self, jax.tree.map(spec, args)
    return _prefill_chunk(self, *args)


_init = _engine.ContinuousBatchingEngine.__init__


def init(self, model=None, *args, **kw):
    _init(self, model, *args, **kw)
    seen["engine"], seen["model"] = self, model


_reduce = trace_reduce.reduce


def reduce(loaded, *args, **kw):
    out = _reduce(loaded, *args, **kw)
    be, eng = seen.get("backend"), seen.get("engine")
    if be is None or eng is None:
        return out
    text = be._chunk_jit.lower(be._pv, be._bv,
                               *seen["args"]).compile().as_text()
    chunks, secs = out.get("modules", {}).get("jit_chunk_fn", (0, 0.0))
    scopes = trace_scopes.seconds_by_scope(loaded, "jit_chunk_fn", text,
                                           SCOPES)
    kernels = trace_kernels.seconds_by_prefix(loaded, KERNELS)
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           seen["workload"] + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           cell["config"] + ".json")) as f:
        cfg = json.load(f)
    experts = cfg.get("n_routed_experts", cfg.get("num_experts"))
    layers = sum(isinstance(m, DroplessMoE)
                 for m in seen["model"].sublayers())
    expert_bytes = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2
    hits = eng.prefill_moe_expert_hits / max(1, eng.prefill_chunks)
    events, kernel_s = kernels["moe_grouped_experts_prefill"]
    split = {
        "chunks": chunks, "chunk_s": secs,
        "chunk_ms": 1e3 * secs / max(1, chunks),
        "scopes": {s: {"events": n, "s": t, "share": t / secs if secs else
                       None} for s, (n, t) in scopes.items()},
        "outside_scopes_s": secs - sum(t for _, t in scopes.values()),
        "kernels": {p: {"events": n, "s": t} for p, (n, t) in
                    kernels.items()},
        "expert_layers": layers, "experts": experts,
        "hits_per_chunk": hits,
        "prefill_kernel_engagement": events / max(1, chunks * layers),
        "prefill_kernel_hit_bytes_roofline_pct":
            100 * hits * expert_bytes * chunks / HBM / kernel_s
            if kernel_s else None}
    print("chunk split " + json.dumps(split), flush=True)
    return out


def install(workload):
    seen["workload"] = workload
    paging.PagedModelStepBackend.prefill_chunk = prefill_chunk
    _engine.ContinuousBatchingEngine.__init__ = init
    trace_reduce.reduce = reduce


if __name__ == "__main__":
    install(sys.argv[sys.argv.index("--workload") + 1])
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + sys.argv[1:] \
        + ["--trace", "1"]
    runpy.run_path(sys.argv[0], run_name="__main__")
