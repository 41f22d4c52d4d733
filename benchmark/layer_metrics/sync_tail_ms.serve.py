"""Engine: host time a tick spends in ``serving.decode_sync`` AFTER its first
fetch returned (``dur - first_ns``): round trips to a drained device, one a
further output of the block. Over the window's untraced part, mean per
``serving.tick``; stalled syncs (``stall=1``) left out and logged."""
from benchmark.window_spans import sync_tail_ms as read  # noqa: F401
