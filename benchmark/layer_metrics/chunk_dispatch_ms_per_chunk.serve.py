"""Engine: mean duration of the ``serving.prefill_chunk`` spans (chunk
operands, four uploads, the enqueue) over the window's untraced part."""
from benchmark.window_spans import chunk_dispatch_ms_per_chunk as read  # noqa: F401,E501
