"""Load generator: mean time from the end of one ``serving.tick`` span to the
start of the next, over the window's untraced part: host time a tick PERIOD
holds outside every program span (``kinds/serve.py`` submits what is due and
samples the live KV between two ticks; in a deployment, the front end's
submit path)."""
from benchmark.window_spans import between_ticks_ms as read  # noqa: F401
