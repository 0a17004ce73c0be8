"""Kernel wrappers: padded input bytes shipped to the device per per-item validity launch, kB (program counters valid_h2d_bytes / valid_launches)."""
from bench.stages import valid_h2d_kb as read  # noqa: F401
