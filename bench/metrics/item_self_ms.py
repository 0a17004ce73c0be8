"""Session per-item decide: mean self time of one item_decide span (mask build, strategy select, glue), ms (program spans)."""
from bench.stages import item_self_ms as read  # noqa: F401
