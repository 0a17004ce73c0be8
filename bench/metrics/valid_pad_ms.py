"""Kernel wrappers: mean host padding of the per-item validity pass's nine inputs, per launch, ms (program span valid_pad)."""
from bench.stages import valid_pad_ms as read  # noqa: F401
