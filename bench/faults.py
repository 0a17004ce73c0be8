"""Faults planted in the timed path, one per way the cells can go wrong,
each of which the comparison that decides ``correct`` has to catch.  Each
wraps ``Platform.decide_batch``; :func:`plant` binds one to a platform
instance after its warm-up, so the window, and only the window, runs it."""
from __future__ import annotations

import types


def _wrong_worker(real):
    """An answer altered where it is produced: the wave's first placement
    is reported on another worker."""
    def decide_batch(self, fs, rng=None, **kw):
        got = real(self, fs, rng, **kw)
        for d in got:
            if d.worker is not None:
                ws = self.state.workers()
                d.worker = ws[(ws.index(d.worker) + 1) % len(ws)]
                break
        return got
    return decide_batch


def _half_left_out(real):
    """Half of the wave left out: only the first half (rounded down) is
    decided and answered."""
    def decide_batch(self, fs, rng=None, **kw):
        return real(self, fs[:len(fs) // 2], rng, **kw)
    return decide_batch


def _state_unchanged(real):
    """A step that returns its state unchanged: the wave is decided and
    answered, but its placements are taken back out of the cluster."""
    def decide_batch(self, fs, rng=None, **kw):
        got = real(self, fs, rng, **kw)
        for d in got:
            if d.worker is not None:
                self.state.complete(d.activation_id)
        return got
    return decide_batch


FAULTS = {"answer-altered": _wrong_worker, "half-left-out": _half_left_out,
          "state-unchanged": _state_unchanged}


def plant(plat, name: str) -> None:
    """Run fault ``name`` in ``plat``'s later ``decide_batch`` calls."""
    real = type(plat).decide_batch
    plat.decide_batch = types.MethodType(FAULTS[name](real), plat)
