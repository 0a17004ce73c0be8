"""Kernels: the per-item validity pass's share of its roofline, % (device trace)."""
from bench.readers import item_roofline_pct as read  # noqa: F401
