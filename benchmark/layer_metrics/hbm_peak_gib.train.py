"""Device: peak HBM in use after the window (``common.hbm_peak_gib``)."""
from benchmark.common import hbm_peak_gib as read  # noqa: F401
