"""Readers of the program's own stage timers (``repro.obs.StageTimers``,
``sched.stage.*``), which a traced run attaches at one sample in one: the
per-item decide path's spans and counters from the zone router down to the
kernel wrapper and the facade's commit.  Each returns ``None`` when the
program under test records no such stage, so a program that lacks one
leaves the metric out of the result line."""
from __future__ import annotations

from typing import Optional

PREFIX = "sched.stage"


def _snapshot(run) -> Optional[dict]:
    return None if run.obs is None else run.obs.registry.snapshot()


def _mean_ms(run, stage: str) -> Optional[float]:
    """Mean duration of the stage's spans over the window, ms."""
    snap = _snapshot(run)
    n = snap.get(f"{PREFIX}.{stage}_s.count", 0) if snap else 0
    if not n:
        return None
    return snap[f"{PREFIX}.{stage}_s.sum"] / n * 1e3


def _self_ms(run, stage: str) -> Optional[float]:
    """Mean self time of the stage's spans: their duration less what their
    child spans cover (the program's ``<stage>_inner_s``), ms."""
    snap = _snapshot(run)
    inner = snap.get(f"{PREFIX}.{stage}_inner_s") if snap else None
    n = snap.get(f"{PREFIX}.{stage}_s.count", 0) if snap else 0
    if inner is None or not n:
        return None
    return (snap[f"{PREFIX}.{stage}_s.sum"] - inner) / n * 1e3


def valid_pad_ms(run) -> Optional[float]:
    return _mean_ms(run, "valid_pad")


def valid_launch_ms(run) -> Optional[float]:
    return _mean_ms(run, "valid_launch")


def valid_fetch_ms(run) -> Optional[float]:
    return _mean_ms(run, "valid_fetch")


def valid_h2d_kb(run) -> Optional[float]:
    """Bytes of padded input shipped per validity launch, kB."""
    snap = _snapshot(run)
    n = snap.get(f"{PREFIX}.valid_launches", 0) if snap else 0
    if not n:
        return None
    return snap[f"{PREFIX}.valid_h2d_bytes"] / n / 1e3


def item_self_ms(run) -> Optional[float]:
    return _self_ms(run, "item_decide")


def route_self_ms(run) -> Optional[float]:
    return _self_ms(run, "shard_route")


def commit_ms(run) -> Optional[float]:
    return _mean_ms(run, "commit")
