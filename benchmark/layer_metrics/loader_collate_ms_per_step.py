"""Trainer: time ``next(loader)`` spends on the caller's thread after the
batch arrived — ``io.loader_unpickle`` and ``io.loader_collate`` (``np.stack``
+ ``to_tensor``, the host-to-device copy), mean per ``train.step_dispatch``
of the traced interval."""
from benchmark.span_metrics import mean_self_ms


def read(ctx):
    return mean_self_ms(ctx, ("io.loader_unpickle", "io.loader_collate"),
                        per="train.step_dispatch")
