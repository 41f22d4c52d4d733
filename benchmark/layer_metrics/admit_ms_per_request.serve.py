"""Engine: mean duration of the ``serving.admit`` spans that admitted (those
with ``keyed_ns``) over the window's untraced part; the log line splits it at
the marks: ``reserved_ns`` (lookup, allocation, table row), ``keyed_ns``
(``PRNGKey`` + ``split``), the rest (scalar uploads, the job)."""
from benchmark.window_spans import admit_ms_per_request as read  # noqa: F401
