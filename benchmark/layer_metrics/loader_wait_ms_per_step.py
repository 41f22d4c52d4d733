"""Trainer: time ``next(loader)`` spends blocked on a worker's ring
(``io.loader_wait``; a poll that timed out counts too), mean per
``train.step_dispatch`` of the traced interval."""
from benchmark.span_metrics import mean_self_ms


def read(ctx):
    return mean_self_ms(ctx, ("io.loader_wait",), per="train.step_dispatch")
