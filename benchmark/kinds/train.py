"""How a training cell is set up, warmed, measured and checked.

``TrainStep`` + AdamW in the setting the configuration states, fed by
``paddle.io.DataLoader`` workers from a fixed set of random-token sequences
made from ``--seed``. Every step ends by fetching its loss, so a step's time
is the device's, and the window opens and closes on step boundaries.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark import weights

SPANS = ("next_batch", "train_step")


def run(ctx) -> dict:
    import jax
    from jax.profiler import TraceAnnotation
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.ops.pallas import flash_attention as fa

    cfg, cell, say = ctx.config, ctx.cell, ctx.say
    dep, tr = cfg["deployment"], cell["traffic"]
    seq, batch = int(tr["seq_len"]), int(tr["batch"])
    clock = time.perf_counter

    t = clock()
    model = weights.build_stacked(weights.llama_config(
        cfg, recompute=dep["recompute"], scan_layers=dep["scan_layers"]),
        ctx.seed)
    jax.block_until_ready([p._value for p in model.parameters()])
    ctx.split["weights_s"] = clock() - t
    t = clock()
    ref_checks = ctx.reference(model) if ctx.reference is not None else {}
    ctx.split["reference_s"] = clock() - t
    t = clock()
    opt = optimizer.AdamW(
        learning_rate=dep["learning_rate"], weight_decay=dep["weight_decay"],
        parameters=model.parameters(),
        multi_precision=dep["multi_precision"])
    step = TrainStep(model, lambda m, b: m(b[0], b[1])[0], opt)
    say(f"trainer: {model.num_params() / 1e6:.1f}M parameters, "
        f"{cfg['num_hidden_layers']} layers, batch {batch} x seq {seq}, "
        f"AdamW multi_precision {dep['multi_precision']}, recompute "
        f"{dep['recompute']}, scan_layers {dep['scan_layers']}")

    rs = np.random.default_rng([ctx.seed & 0xFFFFFFFF, ctx.seed >> 32, 11])
    tokens = rs.integers(0, cfg["vocab_size"],
                         (int(tr["sequences"]), seq + 1), np.int32)

    class FixedTokens(paddle.io.Dataset):
        """The fixed set, cycled: one epoch longer than any run, so the
        workers are forked once (a new epoch would fork them again)."""

        def __len__(self):
            return len(tokens) * 64

        def __getitem__(self, i):
            row = tokens[i % len(tokens)]
            return row[:-1], row[1:]

    # workers fork from a parent that holds the chip; they read numpy rows
    # and never touch jax (proven in PR 21)
    loader = paddle.io.DataLoader(
        FixedTokens(), batch_size=batch, shuffle=False,
        num_workers=int(tr["loader_workers"]), timeout=120)
    it = iter(loader)
    ctx.split["engine_s"] = clock() - t

    losses, waits = [], []

    def one_step():
        with TraceAnnotation("next_batch"):
            t0 = clock()
            b = next(it)
            waits.append(clock() - t0)
        with TraceAnnotation("train_step"):
            losses.append(float(step(tuple(b)).item()))
        return clock()

    try:
        t = clock()
        for _ in range(int(cell["warm_steps"])):
            now = one_step()
        ctx.split["compile_warm_s"] = clock() - t
        ctx.split["warm_traffic_s"] = 0.0
        n0, m0, t0 = len(losses), ctx.meter.snapshot(), now
        ctx.window_opens(t0)
        t_end = t0 + ctx.seconds
        trace_from = t_end - float(cell["trace_s"]) if ctx.trace else None
        while now < t_end:
            if trace_from is not None and now >= trace_from:
                ctx.start_trace()
                trace_from = None
            now = one_step()
        m1 = ctx.meter.snapshot()
        if ctx.trace:
            ctx.stop_trace(SPANS)
    finally:
        it.close()                  # stops and joins the forked workers
    elapsed = now - t0
    steps = len(losses) - n0
    e2e = {"train_tokens_per_s": steps * batch * seq / elapsed}
    say(f"window: {elapsed:.3f} s, {steps} steps of {batch * seq} tokens, "
        f"{elapsed / steps * 1e3:.1f} ms a step; losses first "
        f"{losses[0]:.4f}, last five {[round(x, 4) for x in losses[-5:]]}")

    target = math.log(cfg["vocab_size"])
    route = fa.sdpa_last_dispatch()
    compiles = m1["compiles"] - m0["compiles"]
    checks = {
        "all losses finite": all(math.isfinite(x) for x in losses),
        f"first loss {losses[0]:.3f} within 0.5 of ln(vocab) = {target:.3f}":
            abs(losses[0] - target) <= 0.5,
        "mean of the last five losses below the first":
            float(np.mean(losses[-5:])) < losses[0],
        f"training attention ran a Pallas route ({route})":
            ctx.rehearse or route in ("jax_flash", "splash", "fused_flash"),
        f"no compilation inside the window ({compiles})": compiles == 0,
    }
    checks.update(ref_checks)
    return {
        "e2e": e2e, "attempted": steps,
        "failed": sum(1 for x in losses[n0:] if not math.isfinite(x)),
        "checks": checks,
        "window": {"elapsed_s": elapsed, "steps": steps,
                   "data_wait_s": waits[n0:], "seq_len": seq,
                   "train_module": "jit_step"},
    }
