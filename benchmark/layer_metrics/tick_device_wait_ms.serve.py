"""Engine: host time a tick spends blocked on the device — the self times
of ``serving.prefill_sync`` (``int(tok0)`` after a prompt's last chunk) and
``serving.decode_sync`` (the four transfers after a decode block), mean per
``serving.tick`` of the traced interval."""
from benchmark.span_metrics import mean_self_ms


def read(ctx):
    return mean_self_ms(ctx, ("serving.prefill_sync",
                              "serving.decode_sync"), per="serving.tick")
