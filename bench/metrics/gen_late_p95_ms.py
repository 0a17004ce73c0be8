"""Load generator: 95th percentile of how late it handed over arrivals that fell due while it slept, ms (host clock)."""
from bench.readers import gen_late_p95_ms as read  # noqa: F401
