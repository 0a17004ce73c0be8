#!/usr/bin/env python3
"""Smoke run of the scheduler's decision path on one TPU.

Stands one flat deployment up through ``Platform.from_yaml(...,
backend="pallas")`` -- 16384 workers of 4096 MB, 256 functions over 64
tags, a warm pool, about half the memory already taken -- and commits
waves of 512 arrivals with ``decide_batch(..., apply=True)``, which runs the
fused bulk decide kernel once per wave, then a short tail of single
``invoke`` calls, which runs the per-item validity kernel.  The same
arrivals are replayed one ``invoke`` at a time on a twin
``Platform(backend="np")`` built from the same seed: the plain per-item
reference, independent of the kernels.  Every decision must place on the
same worker, and no wave may leave the bulk path.

Run it on a machine with a TPU:

    python chip_smoke.py [--seed N]

It refuses any other device and exits non-zero; it never falls back to
the CPU or to the Pallas interpreter.  JAX's compile cache stays in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/``
next to this file.  The timings it prints are smoke numbers, not
measurements.  The last line of its output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.core.state import ClusterState, Registry  # noqa: E402
from repro.platform import Platform  # noqa: E402
from repro.pool import StartCosts, WarmPool, make_policy  # noqa: E402

STRATEGIES = ("best_first", "least_loaded", "warmest", "min_cost")
WORKER_MB = 4096
KEEP_ALIVE_S = 600.0  # idle containers outlive the run: warmth only grows
WAVE_GAP_S = 1.0  # platform time between waves; hot window is 2 s
COMPLETE_FRAC = 0.3  # share of a wave's activations that finish after it


def script_text(n_tags: int) -> str:
    """One wildcard policy per tag under the four vectorised strategies:
    affinity with an unconstrained fallback block, anti-affinity, and one
    ``capacity_used`` invalidate (80% of 4096 MB is 3276.8 MB, which no
    integer-MB usage can equal, so float32 and float64 agree)."""
    out = []
    for i in range(n_tags):
        strat = STRATEGIES[i % len(STRATEGIES)]
        tag = f"t{i:03d}"
        if i == 0:
            out.append(f"{tag}:\n  workers: *\n  strategy: {strat}\n"
                       "  invalidate:\n    - capacity_used 80%\n")
        elif i % 4 == 1:
            out.append(f"{tag}:\n  - workers: *\n    strategy: {strat}\n"
                       f"    affinity: [t{i - 1:03d}]\n"
                       f"  - workers: *\n    strategy: {strat}\n")
        elif i % 4 == 2:
            out.append(f"{tag}:\n  workers: *\n  strategy: {strat}\n"
                       f"  affinity: [!t{(i + 1) % n_tags:03d}]\n")
        else:
            out.append(f"{tag}:\n  workers: *\n  strategy: {strat}\n")
    return "".join(out)


def deployment(seed: int, *, workers: int, functions: int, tags: int):
    """Function specs ``{name: (memory MB, tag)}`` and the pre-existing
    activations ``[(function, worker)]`` that fill each worker to a uniform
    random share of its memory (about half of the cluster overall)."""
    rng = np.random.default_rng(seed)
    mems = rng.integers(128, 2049, functions)
    specs = {f"f{i:04d}": (int(mems[i]), f"t{i % tags:03d}")
             for i in range(functions)}
    names = list(specs)
    preload = []
    targets = rng.random(workers) * WORKER_MB
    picks = rng.integers(0, functions, (workers, 16))
    for w in range(workers):
        used = 0
        for k in picks[w]:
            m = specs[names[k]][0]
            if used + m > targets[w]:
                continue
            used += m
            preload.append((names[k], f"w{w:05d}"))
    return specs, preload


def arrivals(seed: int, names, *, waves: int, wave_size: int):
    """Zipf-like popularity (weight 1/rank), as FaaS traces show: a few
    functions take most of the calls."""
    rng = np.random.default_rng(seed + 1)
    p = 1.0 / np.arange(1, len(names) + 1)
    idx = rng.choice(len(names), (waves, wave_size), p=p / p.sum())
    return [[names[k] for k in row] for row in idx]


def build(specs, preload, *, workers: int, tags: int, seed: int,
          backend: str, interpret: bool = False) -> Platform:
    state = ClusterState()
    for w in range(workers):
        state.add_worker(f"w{w:05d}", max_memory=WORKER_MB)
    reg = Registry({f: (m, t) for f, (m, t) in specs.items()})
    for f, w in preload:
        state.allocate(f, w, reg)
    pool = WarmPool(make_policy("fixed_ttl", ttl=KEEP_ALIVE_S),
                    costs=StartCosts(), hot_window=2.0)
    return Platform.from_yaml(script_text(tags), cluster=state, registry=reg,
                              pool=pool, seed=seed, backend=backend,
                              interpret=interpret)


def run(*, workers: int = 16384, functions: int = 256, tags: int = 64,
        waves: int = 8, wave_size: int = 512, tail: int = 32, seed: int = 0,
        interpret: bool = False) -> dict:
    """Drive the device platform and the ``np`` twin through the same
    arrivals.  Returns a report; :func:`failures` reads it."""
    specs, preload = deployment(seed, workers=workers, functions=functions,
                                tags=tags)
    waves_fs = arrivals(seed, list(specs), waves=waves + 1,
                        wave_size=wave_size)
    tail_fs = waves_fs.pop()[:tail]
    dev = build(specs, preload, workers=workers, tags=tags, seed=seed,
                backend="pallas", interpret=interpret)
    ref = build(specs, preload, workers=workers, tags=tags, seed=seed,
                backend="np")
    pick = random.Random(seed + 2)
    rng_dev, rng_ref = random.Random(seed + 3), random.Random(seed + 3)
    report = {"waves": [], "mismatches": [], "rows": [], "starts": {}}
    for k, fs in enumerate(waves_fs):
        t0 = time.perf_counter()
        got = dev.decide_batch(fs, rng_dev, apply=True)
        report["waves"].append(time.perf_counter() - t0)
        want = [ref.invoke(f, rng_ref) for f in fs]
        report["rows"].append(len(set(fs)))
        for d in got:
            kind = d.start_kind or "unplaced"
            report["starts"][kind] = report["starts"].get(kind, 0) + 1
        for i, (g, w) in enumerate(zip(got, want)):
            if g.worker != w.worker:
                report["mismatches"].append((k, i, g.function, g.worker,
                                             w.worker))
        # some of the wave finishes: containers park idle, so the warmth
        # column of later waves has hot and warm entries
        done = [i for i, d in enumerate(got) if d.worker is not None
                and pick.random() < COMPLETE_FRAC]
        for i in done:
            dev.complete(got[i])
            ref.complete(want[i])
        dev.advance(WAVE_GAP_S)
        ref.advance(WAVE_GAP_S)
    t0 = time.perf_counter()
    for i, f in enumerate(tail_fs):  # the per-item kernel
        g, w = dev.invoke(f, rng_dev), ref.invoke(f, rng_ref)
        if g.worker != w.worker:
            report["mismatches"].append((waves, i, f, g.worker, w.worker))
    report["tail_s"] = time.perf_counter() - t0
    report["decisions"] = waves * wave_size + len(tail_fs)
    report["stats"] = dict(dev.session.stats)
    report["interpret"] = dev.session.interpret
    report["n_waves"] = waves
    report["occupancy"] = sum(
        a.memory for a in dev.state.active_activations()) / (
            workers * WORKER_MB)
    dev.close()
    ref.close()
    return report


def failures(report: dict):
    out = [f"wave {k} item {i} ({f}): pallas placed on {g}, np reference "
           f"on {w}" for k, i, f, g, w in report["mismatches"][:10]]
    if report["mismatches"]:
        out.append(f"{len(report['mismatches'])} decisions differ in all")
    st = report["stats"]
    if st["bulk_fallback"] != 0:
        out.append(f"bulk_fallback = {st['bulk_fallback']}, expected 0")
    if st["bulk_waves"] != report["n_waves"]:
        out.append(f"bulk_waves = {st['bulk_waves']}, expected "
                   f"{report['n_waves']}")
    return out


def require_tpu(devices):
    """The smoke run is for the chip: anything else is refused."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found {len(devices)} "
            f"{dev.platform} device(s) ({dev.device_kind}); not falling back")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.kernels.compile_cache import use_compile_cache

    dev = require_tpu(jax.devices())
    cache = use_compile_cache(ROOT / ".jax_cache")
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event.endswith("backend_compile_duration") else None)

    report = run(seed=args.seed)
    bad = failures(report)
    for line in bad:
        print(f"chip_smoke: FAIL {line}", file=sys.stderr)
    if bad:
        return 1

    st = report["stats"]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"smoke numbers (not measurements), {dev.platform} "
          f"{dev.device_kind}, seed {args.seed}, compile cache {cache}")
    print(f"smoke: {report['decisions']} decisions match the np reference; "
          f"pallas interpret={report['interpret']}; "
          f"bulk_waves={st['bulk_waves']} "
          f"bulk_fallback={st['bulk_fallback']}; memory occupied at end "
          f"{report['occupancy']:.3f}; wave starts {report['starts']}")
    for k, (s, r) in enumerate(zip(report["waves"], report["rows"])):
        print(f"smoke: wave {k} distinct functions {r} wall_s {s!r}")
    print(f"smoke: tail of single invokes wall_s {report['tail_s']!r}")
    print(f"smoke: backend compile total_s {sum(compile_s)!r} "
          f"({len(compile_s)} programs)")
    print(f"smoke: device peak_bytes_in_use {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
