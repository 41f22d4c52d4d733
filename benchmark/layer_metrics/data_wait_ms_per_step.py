"""Trainer: host time spent in ``next(loader)`` per step of the window."""


def read(ctx):
    waits = ctx.window.get("data_wait_s")
    return sum(waits) / len(waits) * 1e3 if waits else None
