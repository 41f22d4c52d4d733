"""Serving loop: host time a tick spends choosing work — the self times of
``serving.expire`` (deadline scan), ``serving.schedule`` (preemption walk +
``scheduler.pop_ready``) and ``serving.admit`` (block allocation, prefix
lookup, slot arming), mean per ``serving.tick`` of the traced interval."""
from benchmark.span_metrics import mean_self_ms


def read(ctx):
    return mean_self_ms(ctx, ("serving.expire", "serving.schedule",
                              "serving.admit"), per="serving.tick")
