"""Facade commit and warm pool: mean commit of one placed decision (allocate with its state deltas, pool acquire), ms (program span commit)."""
from bench.stages import commit_ms as read  # noqa: F401
