"""Device: share of the traced span with no operation running
(``common.device_idle_pct``)."""
from benchmark.common import device_idle_pct as read  # noqa: F401
