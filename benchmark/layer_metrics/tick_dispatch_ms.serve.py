"""Engine: host time a tick spends building operands and enqueueing device
work — the self times of ``serving.prefill_chunk`` (chunk operands, uploads,
enqueue) and ``serving.decode_block`` (the enqueue only), mean per
``serving.tick`` of the traced interval."""
from benchmark.span_metrics import mean_self_ms


def read(ctx):
    return mean_self_ms(ctx, ("serving.prefill_chunk",
                              "serving.decode_block"), per="serving.tick")
