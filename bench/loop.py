"""The open loop: arrivals are handed to the platform when they are due,
whatever the platform is doing, on the host's wall clock.

One thread runs the controller.  Each turn it delivers the completions that
are due (``Platform.complete``), then hands every arrival that is due and not
yet decided, oldest first and at most ``max_wave`` of them, to
``Platform.decide_batch(..., apply=True)``; on a zoned cluster the wave is
split by origin zone, one call per zone in the order of each zone's oldest
arrival.  With nothing due it sleeps until the next arrival or completion.

The platform's clock reads the window's wall clock (seconds since the
window opened) as the controller last set it: at the start of each call,
so one call sees one instant, and warm-pool hot windows are real seconds.

An arrival's latency runs from its due time to the return of the call that
decided it.  An arrival due in the window and undecided when it closes
counts at its age then, so a stall cannot hide.  Everything the platform
was told and answered is logged for the reference's replay.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import random
import time
from typing import Dict, List, Optional, Tuple

from .traffic import Arrival, children


@dataclasses.dataclass
class Log:
    """What one window did, in the order it happened.

    ``events``: ``("call", t, zone, ids, answers)`` with ``answers`` a list of
    ``(worker or None, start kind or None)``, and ``("complete", t, ids)``."""
    seconds: float
    events: List[tuple] = dataclasses.field(default_factory=list)
    arrivals: Dict[int, Arrival] = dataclasses.field(default_factory=dict)
    decided_at: Dict[int, float] = dataclasses.field(default_factory=dict)
    gen_late: List[float] = dataclasses.field(default_factory=list)
    calls: List[Tuple[float, float, int]] = dataclasses.field(
        default_factory=list)  # (start, end, arrivals) per decide_batch call
    t_stop: float = 0.0
    running: List[object] = dataclasses.field(default_factory=list)

    def latencies(self) -> List[float]:
        """Seconds from due to decision for every arrival due in the window;
        an undecided one at its age when the window closed."""
        out = []
        for a in self.arrivals.values():
            if a.due >= self.seconds:
                continue
            t = self.decided_at.get(a.id)
            out.append((t if t is not None else self.t_stop) - a.due)
        return out

    def attempted(self) -> int:
        return sum(1 for a in self.arrivals.values() if a.due < self.seconds)

    def undecided(self) -> int:
        return sum(1 for a in self.arrivals.values()
                   if a.due < self.seconds and a.id not in self.decided_at)


class Clock:
    """The platform's clock: the window time the controller last set."""

    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


def run(plat, dep, roots: List[Arrival], *, seconds: float, max_wave: int,
        rng: random.Random, clock: Clock, annotate=None) -> Log:
    """Drive ``plat`` for ``seconds`` of wall time.  ``annotate(name)``
    returns a context manager for a host span (the profiler's, in a traced
    run); ``None`` records none."""
    span = annotate or (lambda name: contextlib.nullcontext())
    zoned = len(dep.zones) > 1
    zone_of = dict(zip(dep.workers, dep.zone))
    log = Log(seconds)
    for a in roots:
        log.arrivals[a.id] = a
    later: List[Tuple[float, int]] = []  # spawned arrivals not yet due
    next_id = len(roots)
    ready: List[Arrival] = []
    done: List[Tuple[float, int, object]] = []  # (due, arrival id, decision)
    k = 0  # next root to become due
    perf = time.perf_counter
    t0 = perf()
    waited_from = None
    while True:
        now = perf() - t0
        if now >= seconds:
            break
        if done and done[0][0] <= now:
            ids, decs = [], []
            while done and done[0][0] <= now:
                _t, aid, d = heapq.heappop(done)
                ids.append(aid)
                decs.append(d)
            clock.now = now
            with span("bench.complete"):
                for d in decs:
                    plat.complete(d)
            log.events.append(("complete", now, ids))
        n0 = len(ready)
        while k < len(roots) and roots[k].due <= now:
            ready.append(roots[k])
            k += 1
        while later and later[0][0] <= now:
            ready.append(log.arrivals[heapq.heappop(later)[1]])
        if waited_from is not None:
            # handed over late by the generator itself: due while it slept
            log.gen_late.extend(now - a.due for a in ready[n0:]
                                if a.due > waited_from)
            waited_from = None
        if not ready:
            nxt = seconds
            if k < len(roots):
                nxt = min(nxt, roots[k].due)
            if later:
                nxt = min(nxt, later[0][0])
            if done:
                nxt = min(nxt, done[0][0])
            waited_from = now
            with span("bench.wait"):
                time.sleep(max(0.0, nxt - (perf() - t0)))
            continue
        ready.sort(key=lambda a: (a.due, a.id))
        wave, ready = ready[:max_wave], ready[max_wave:]
        groups: Dict[Optional[str], List[Arrival]] = {}
        for a in wave:
            groups.setdefault(a.origin if zoned else None, []).append(a)
        for zone, group in groups.items():
            start = perf() - t0
            clock.now = start
            with span("bench.decide_batch"):
                got = plat.decide_batch([a.function for a in group], rng,
                                        apply=True, zone=zone)
            end = perf() - t0
            log.calls.append((start, end, len(group)))
            log.events.append(("call", start, zone, [a.id for a in group],
                               [(d.worker, d.start_kind) for d in got]))
            for a, d in zip(group, got):
                log.decided_at[a.id] = end
                if d.worker is None:
                    continue
                heapq.heappush(done, (end + dep.functions[a.function].duration,
                                      a.id, d))
                for c in children(dep, a, end, zone_of[d.worker], next_id):
                    log.arrivals[c.id] = c
                    heapq.heappush(later, (c.due, c.id))
                    next_id = c.id + 1
    log.t_stop = now
    log.running = [d for _t, _a, d in done]  # placed, not yet completed
    return log
