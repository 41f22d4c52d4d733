"""Serving loop: host time a tick spends after the device answered — the
self times of ``serving.harvest`` (tokens to runs, retirement, results),
``serving.deliver`` (handing tokens to ``stream_sink``) and what is left of
``serving.tick`` itself (fault point, clocks, flight record), mean per
``serving.tick`` of the traced interval. With the three other
``tick_*_ms.serve`` metrics it adds up to the mean tick span."""
from benchmark.span_metrics import mean_self_ms


def read(ctx):
    return mean_self_ms(ctx, ("serving.harvest", "serving.deliver",
                              "serving.tick"), per="serving.tick")
