"""Device: share of the traced window in which no operation ran, below the knee, % (device trace)."""
from bench.readers import device_idle_pct as read  # noqa: F401
