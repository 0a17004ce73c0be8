"""A rehearsal of a cell on the path the chip runs -- ``backend="pallas"``,
here in the Pallas interpreter -- at a tiny size: the bulk kernel, its
wrapper and the wave commit loop (the flat deployment), and the zone router
and per-item kernel (the zoned one), agree with the plain reference, and an
answer altered where it is produced makes the run not correct."""
from __future__ import annotations

import time
from pathlib import Path

import pytest

from bench import harness
from repro.platform import Platform

import bench_tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = harness.load_benchmark(ROOT)


def run(name="azure16k-steady"):
    return harness.run_cell(SPEC, bench_tiny.cell(SPEC, name),
                            seed=2**33 + 1, seconds=0.5, trace=False,
                            t_start=time.perf_counter(), backend="pallas",
                            interpret=True)


@pytest.fixture(autouse=True)
def tiny(monkeypatch):
    bench_tiny.install(monkeypatch)


def test_interpret_rehearsal_is_correct():
    res = run()
    assert res["correct"], res["_stderr"]
    assert "'bulk_fallback': 0" in res["_stderr"][3]


def test_interpret_rehearsal_catches_a_wrong_placement(monkeypatch):
    real = Platform.decide_batch

    def decide_batch(self, fs, rng=None, **kw):
        got = real(self, fs, rng, **kw)
        placed = [d for d in got if d.worker is not None]
        if placed:
            ws = self.state.workers()
            d = placed[-1]
            d.worker = ws[(ws.index(d.worker) + 3) % len(ws)]
        return got

    monkeypatch.setattr(Platform, "decide_batch", decide_batch)
    res = run()
    assert not res["correct"]
    assert res["checks"]["worker_mismatches"]["value"] > 0


def test_interpret_rehearsal_of_the_zoned_cell_is_correct():
    res = run("divimp2z-steady")
    assert res["correct"], res["_stderr"]
    assert res["checks"]["worker_mismatches"]["value"] == 0
    assert "'zone_hops'" in res["_stderr"][3]
