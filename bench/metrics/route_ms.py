"""Zone router: mean of the program's shard_route stage timer over the window, ms (every decision timed)."""
from bench.readers import route_ms as read  # noqa: F401
