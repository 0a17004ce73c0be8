"""Kernel wrappers: mean wait for the per-item validity result, unpad and copy to the host, ms (program span valid_fetch)."""
from bench.stages import valid_fetch_ms as read  # noqa: F401
