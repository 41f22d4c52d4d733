"""Engine: time a tick leaves the chip with an empty queue while the engine
holds work — the ``starved_ns`` the ``serving.prefill_chunk`` and
``serving.decode_block`` spans carry (from the sync that said the device had
drained to the return of that enqueue; ``engine.device_starved_ns`` is their
sum), over the window's untraced part, mean per ``serving.tick``. What host
code can win of the device's idle time; the traced run also logs the account
of its parts (``window_spans.account``)."""
from benchmark.window_spans import device_starved_ms as read  # noqa: F401
