"""The comparison that decides ``correct``: a sound run of the timed path
agrees with the plain reference decision for decision, and a run with the
timed path broken underneath does not -- one run per fault the cells can
have.  The harness's look for a chip is skipped; everything else is a real
run at a tiny size, on the numpy backend."""
from __future__ import annotations

import time
from pathlib import Path

import pytest

from bench import check, faults, harness
from repro.platform import Platform

import bench_tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = harness.load_benchmark(ROOT)
SEED = 2**34 + 99


def run(name, backend="np", interpret=False):
    return harness.run_cell(SPEC, bench_tiny.cell(SPEC, name), seed=SEED,
                            seconds=1.0, trace=False,
                            t_start=time.perf_counter(), backend=backend,
                            interpret=interpret)


@pytest.fixture(autouse=True)
def tiny(monkeypatch):
    bench_tiny.install(monkeypatch)


@pytest.mark.parametrize("name", ["azure16k-steady", "divimp2z-steady"])
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"], res["_stderr"]
    assert res["checks"]["worker_mismatches"]["value"] == 0
    assert res["attempted"] > 20
    assert res["_stderr"][-1].startswith("check ")


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", ["azure16k-steady", "divimp2z-steady"])
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    monkeypatch.setattr(Platform, "decide_batch",
                        faults.FAULTS[fault](Platform.decide_batch))
    res = run(name)
    assert not res["correct"]
    assert sum(c["value"] for c in res["checks"].values()) > 0


@pytest.mark.parametrize("name", ["azure-region-16k", "paper-divimp-2zone"])
def test_control_fails_and_program_passes_on_the_same_log(name):
    """The control -- the reference with its anti-affinity terms dropped,
    put in the program's place -- fails the comparison the program
    passes, on three seeds."""
    cell = {"azure-region-16k": "azure16k-steady",
            "paper-divimp-2zone": "divimp2z-steady"}[name]
    for seed in (SEED, SEED + 1, SEED + 2):
        stand = harness.stand_up(bench_tiny.cell(SPEC, cell), seed, 1.0,
                                 backend="np")
        log = harness.drive(stand)
        stand.plat.close()
        ok, counts = check.check(stand.dep, log, seed)
        assert ok and counts["worker_mismatches"] == 0
        ctl = check.control_reading(stand.dep, log, seed, "no-anti-affinity")
        assert ctl["worker_mismatches"] > 0


def test_bf16_control_misjudges_a_fit_the_exact_reference_makes():
    """The lower-precision control rounds a worker's used memory to
    bfloat16: 4081 MB becomes 4080, so a 15 MB function fits where exact
    integers (4081 + 15 > 4095) say it does not."""
    import random

    from bench import deploy, reference

    cfg = bench_tiny.tiny_config("azure-region-16k")
    dep = deploy.build(cfg, SEED)
    dep.memory[:] = 4095
    dep.preload = []
    f = next(iter(dep.functions))
    dep.functions[f] = deploy.Function(f, 15, dep.functions[f].tag, 1.0)
    exact = reference.Reference(dep, random.Random(0))
    low = reference.Reference(dep, random.Random(0), "bf16-memory")
    for ref in (exact, low):
        ref.used[0] = 4081
        if ref.bf16:
            ref.used[0] = reference._bf16(ref.used[0])
    blk = reference._Block(dep, {"workers": "*"}, exact.tcol, None)
    cand = blk.cand[:1]
    assert exact._pick(f, blk, cand, 0.0) is None
    assert low._pick(f, blk, cand, 0.0) == 0


def test_sound_run_with_followup_fail_and_concurrency(monkeypatch):
    from bench import deploy

    monkeypatch.setattr(deploy, "load_config", bench_tiny.tiny_variant_config)
    dep = deploy.build(deploy.load_config("azure-region-16k"), SEED)
    text = deploy.script_text(dep)
    assert "  - followup: fail" in text
    assert "max_concurrent_invocations 6" in text
    assert dep.rows("f0003") == 1
    res = run("azure16k-steady")
    assert res["correct"], res["_stderr"]
