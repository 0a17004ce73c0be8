"""The arithmetic behind the per-layer metrics.  Each metric's own file in
``bench/metrics/`` names one of these readers; a reader returns ``None``
when the run holds nothing for it to read, and the metric is then left out
of the result line.  ``wave_ms`` and ``bulk_roofline_pct`` read the flat
deployment's bulk path, whose cell waits for a later entry (PERF.md)."""
from __future__ import annotations

from typing import Optional

import numpy as np

from . import roofline


def gen_late_p95_ms(run) -> Optional[float]:
    """95th percentile of how late the generator handed over arrivals that
    fell due while it slept (host clock)."""
    late = run.log.gen_late
    if len(late) < 20:
        return None
    return float(np.percentile(late, 95)) * 1e3


def wave_ms(run) -> Optional[float]:
    """Mean wall of one ``decide_batch`` call: the harness's
    ``bench.decide_batch`` spans in the trace."""
    if run.trace is None:
        return None
    d = [e - s for n, s, e in run.trace.spans if n == "bench.decide_batch"]
    return float(np.mean(d)) * 1e3 if d else None


def route_ms(run) -> Optional[float]:
    """Mean of the program's ``sched.stage.shard_route_s`` stage timer over
    the window (every decision timed)."""
    if run.obs is None:
        return None
    snap = run.obs.registry.snapshot()
    n = snap.get("sched.stage.shard_route_s.count", 0)
    if not n:
        return None
    return snap["sched.stage.shard_route_s.sum"] / n * 1e3


def device_idle_pct(run) -> Optional[float]:
    if run.trace is None or not run.trace.devices or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)


def _share(run, kernel: str, shapes) -> Optional[float]:
    """Roofline share: the least time the chip could take for every call
    (``shapes``: one ``(R, W, T)`` per device event, in order) over the
    kernel's device time in the trace."""
    if run.trace is None:
        return None
    times = run.trace.kernel_events(kernel)
    if not times or len(times) != len(shapes):
        return None
    peak = roofline.peaks(run.device_kind)
    bound = sum(roofline.bound_s(kernel, R, W, T, peak)[0]
                for R, W, T in shapes)
    return 100.0 * bound / sum(times)


def bulk_roofline_pct(run) -> Optional[float]:
    """The fused bulk pass: one launch per call of two or more arrivals,
    over R = the chain rows of the call's distinct functions."""
    dep = run.dep
    W, T = len(dep.workers), len(run.tags)
    shapes = []
    for ev in run.log.events:
        if ev[0] == "call" and len(ev[3]) > 1:
            fs = {run.log.arrivals[a].function for a in ev[3]}
            shapes.append((sum(dep.rows(f) for f in fs), W, T))
    return _share(run, "bulk_decide_kernel", shapes)


def item_roofline_pct(run) -> Optional[float]:
    """The per-item validity pass: one launch per zone hop of a routed
    decision (over the zone's workers) and per delegated decision (over the
    whole cluster), each over its function's chain rows.  Read only where
    the routed functions share one chain length, the delegated ones
    another, and the zones one size."""
    dep = run.dep
    if len(dep.zones) < 2 or run.stats is None:
        return None
    sizes = {dep.zone.count(z) for z in dep.zones}
    routed, flat = set(), set()
    for a in run.log.arrivals.values():
        chain = dep.chain(dep.functions[a.function].tag)
        (routed if any(b.get("topology") for b in chain) else flat).add(
            dep.rows(a.function))
    if len(sizes) != 1 or len(routed) > 1 or len(flat) > 1:
        return None
    hops = run.stats["zone_hops"]
    delegated = run.stats["delegated"]
    T = len(run.tags)
    shapes = ([(routed.pop(), sizes.pop(), T)] * hops if hops else []) + (
        [(flat.pop(), len(dep.workers), T)] * delegated if delegated else [])
    times = run.trace.kernel_events("affinity_valid_kernel") \
        if run.trace is not None else []
    if len(times) != len(shapes):
        return None
    # hops and delegated launches interleave; the sum does not depend on
    # their order
    return _share(run, "affinity_valid_kernel", shapes)
