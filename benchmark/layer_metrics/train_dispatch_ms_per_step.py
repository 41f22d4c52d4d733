"""Trainer: host time ``TrainStep.__call__`` spends flattening its
arguments and enqueueing the step (``train.step_dispatch``; not the
caller's wait for the loss), mean per step of the traced interval."""
from benchmark.span_metrics import mean_self_ms


def read(ctx):
    return mean_self_ms(ctx, ("train.step_dispatch",),
                        per="train.step_dispatch")
