"""Zone router: mean self time of one routed decision's shard_route span, its zone hops' item_decide spans left out, ms (program spans)."""
from bench.stages import route_self_ms as read  # noqa: F401
