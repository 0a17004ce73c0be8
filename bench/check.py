"""The comparison that decides ``correct``: every decision the window made
against the plain reference's decision for the same request at the same
point of the same history.

Two numbers are compared, each with its limit (an exact comparison, so the
limit is 0): decisions whose worker differs from the reference's (a request
the reference also leaves unplaced agrees), and decisions whose start kind
differs.  ``unanswered`` counts requests handed over whose answer never came
back (a call that returned fewer decisions than it was given).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import reference

LIMITS = {"worker_mismatches": 0, "start_kind_mismatches": 0,
          "unanswered": 0}


def answers(log) -> List[Tuple[int, Optional[str], Optional[str]]]:
    out = []
    for ev in log.events:
        if ev[0] == "call":
            for aid, (w, kind) in zip(ev[3], ev[4]):
                out.append((aid, w, kind))
    return out


def compare(got, want) -> Dict[str, int]:
    """Counts over the decisions of ``want`` (the reference)."""
    by_id = {aid: (w, k) for aid, w, k in got}
    wm = km = missing = 0
    for aid, w, k in want:
        g = by_id.get(aid)
        if g is None:
            missing += 1
            continue
        if g[0] != w:
            wm += 1
        elif g[1] != k:
            km += 1
    return {"decisions": len(want), "worker_mismatches": wm,
            "start_kind_mismatches": km, "unanswered": missing,
            "unplaced": sum(1 for _a, w, _k in want if w is None)}


def check(dep, log, rng_seed: int) -> Tuple[bool, Dict[str, int]]:
    want = reference.replay(dep, log, rng_seed)
    got = answers(log)
    counts = compare(got, want)
    ok = all(counts[k] <= lim for k, lim in LIMITS.items())
    return ok, counts


def control_reading(dep, log, rng_seed: int, control: str) -> Dict[str, int]:
    """The control put in the program's place: its answers to the same log
    against the reference's."""
    want = reference.replay(dep, log, rng_seed)
    return compare(reference.replay(dep, log, rng_seed, control), want)


def report_lines(counts: Dict[str, int]) -> List[str]:
    return [f"{k} {counts[k]} limit {lim}" for k, lim in LIMITS.items()]
