"""Kernel wrappers: mean call of the per-item validity kernel up to its return (transfers of the padded inputs, dispatch), ms (program span valid_launch)."""
from bench.stages import valid_launch_ms as read  # noqa: F401
