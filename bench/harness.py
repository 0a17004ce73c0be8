"""One run of one cell: set-up, warm-up, window, check, metrics.  Everything
that belongs to one configuration, traffic mix or per-layer metric is read
from its own file by the name ``BENCHMARK.json`` gives it."""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import glob
import json
import os
import random
import shutil
import tempfile
import time
from itertools import product
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import check, deploy, loop, metrics, traffic
from . import trace as trace_mod

E2E_READERS = {
    "decision_p50_ms": lambda run: float(np.percentile(run.lat, 50)) * 1e3,
    "decision_p95_ms": lambda run: float(np.percentile(run.lat, 95)) * 1e3,
    "decisions_per_s": lambda run: len(run.log.decided_at) / run.log.t_stop,
}


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for c in spec["workloads"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"run: no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: dict, group: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    mine = []
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec[group]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                mine.append(m)
        elif group == "end_to_end":
            mine.append(m)
        else:  # reported wherever its end-to-end metric is
            moved = e2e[m["moves"]]
            if "workloads" not in moved or cell["name"] in moved["workloads"]:
                mine.append(m)
    return mine


def use_compile_cache(default_dir: Path) -> str:
    """The program's cache rule (``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``default_dir``, a fixed path in the checkout), keeping every
    program however fast it compiled: the wrappers compile a small program
    per distinct wave shape, which the warm-up loads from there."""
    import jax
    from repro.kernels.compile_cache import use_compile_cache as program_rule

    placed = program_rule(default_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed


# ---- warm-up --------------------------------------------------------------- #

def _waves_for_rows(dep: deploy.Deployment, fs: List[str], max_wave: int):
    """One wave of distinct functions of ``fs`` for every total chain-row
    count R a wave of at most ``max_wave`` arrivals can reach (a single
    function is sent twice, so the wave still takes the bulk pass)."""
    by_rows: Dict[int, List[str]] = collections.defaultdict(list)
    for f in fs:
        by_rows[dep.rows(f)].append(f)
    sizes = sorted(by_rows)
    reach: Dict[int, tuple] = {}
    ranges = [range(min(len(by_rows[s]), max_wave) + 1) for s in sizes]
    for counts in product(*ranges):
        n = sum(counts)
        if n == 0 or n > max_wave:
            continue
        r = sum(c * s for c, s in zip(counts, sizes))
        if r not in reach or n < sum(reach[r]):
            reach[r] = counts
    for r in sorted(reach):
        wave = [f for c, s in zip(reach[r], sizes) for f in by_rows[s][:c]]
        if len(wave) == 1:
            wave = wave * 2
        yield r, wave


def warm_up(plat, dep: deploy.Deployment, mix: dict) -> Dict[str, int]:
    """Drive every shape the cell's traffic can reach through the platform's
    own entry points, without changing its state: the per-item path once per
    function and origin zone (``Platform.decide``), and on a flat cluster the
    bulk pass once for every reachable row count (``decide_batch(...,
    apply=False)``).  A throwaway generator takes the random draws."""
    rng = random.Random(0)
    names = sorted(_mix_functions(dep, mix))
    zones = dep.zones if len(dep.zones) > 1 else [None]
    if zones == [None]:  # the per-item shapes differ only by chain rows
        names_1 = list({dep.rows(f): f for f in names}.values())
    else:
        names_1 = names
    for f in names_1:
        for z in zones:
            plat.decide(f, rng, zone=z)
    n = 0
    if len(dep.zones) <= 1 and mix["max_wave"] > 1:
        for _r, wave in _waves_for_rows(dep, names, mix["max_wave"]):
            plat.decide_batch(wave, rng, apply=False)
            n += 1
    return {"functions": len(names), "bulk_waves": n}


def _mix_functions(dep: deploy.Deployment, mix: dict) -> List[str]:
    if mix["functions"] == "popularity":
        base = [f for f, _w in dep.popularity]
    else:
        zones = list(mix.get("origins") or [""])
        base = [e["function"].format(origin=z) for e in mix["functions"]
                for z in zones]
    out, todo = [], list(base)
    while todo:  # and everything they spawn
        f = todo.pop()
        if f not in out:
            out.append(f)
            todo += [c for c, _n, _d in dep.functions[f].spawns]
    return out


# ---- one run --------------------------------------------------------------- #

@dataclasses.dataclass
class RunData:
    """What the readers of ``bench/metrics`` see."""
    cell: dict
    dep: deploy.Deployment
    mix: dict
    log: loop.Log
    lat: List[float]
    tags: List[str]
    device_kind: str
    trace: Optional[trace_mod.Trace] = None
    obs: object = None
    stats: Optional[Dict[str, int]] = None  # session counters over the window


class _Compiles:
    """Counts programs JAX compiles or loads, from its monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.seconds += secs


@dataclasses.dataclass
class Stand:
    """A cell's system under test, built and warmed, before its window."""
    dep: deploy.Deployment
    mix: dict
    clock: loop.Clock
    plat: object
    warm: Dict[str, int]
    seed: int
    seconds: float
    roots: List[traffic.Arrival]


def stand_up(cell: dict, seed: int, seconds: float, *,
             backend: str = "pallas", interpret: bool = False) -> Stand:
    """The cell's deployment from ``seed``, its platform on the window's
    clock, every shape its traffic can reach warmed, and the window's
    arrivals drawn."""
    mix = traffic.load_mix(cell["traffic"])
    dep = deploy.build(deploy.load_config(cell["config"]), seed)
    clock = loop.Clock()
    plat = deploy.platform(dep, clock, backend=backend, interpret=interpret)
    warm = warm_up(plat, dep, mix)
    return Stand(dep, mix, clock, plat, warm, seed, seconds,
                 traffic.roots(dep, mix, seed, seconds))


def drive(stand: Stand, annotate=None) -> loop.Log:
    """The cell's open-loop window."""
    return loop.run(stand.plat, stand.dep, stand.roots,
                    seconds=stand.seconds, max_wave=stand.mix["max_wave"],
                    rng=random.Random(stand.seed), clock=stand.clock,
                    annotate=annotate)


def run_cell(spec: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, backend: str = "pallas",
             interpret: bool = False) -> dict:
    import jax

    compiles = _Compiles()
    stand = stand_up(cell, seed, seconds, backend=backend,
                     interpret=interpret)
    dep, mix, plat, warm = stand.dep, stand.mix, stand.plat, stand.warm
    obs = None
    if trace:
        from repro.obs import Obs, StageTimers

        obs = Obs()
        obs.timers = StageTimers(obs.registry, sample=1)
        plat.attach_obs(obs)
    stats0 = dict(plat.session.stats)
    tdir = None
    annotate = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    compiled_before = compiles.n
    gc.collect()
    gc.freeze()  # the set-up's objects stay out of the window's collections
    setup_s = time.perf_counter() - t_start
    with (annotate("bench.window") if annotate else contextlib.nullcontext()):
        log = drive(stand, annotate)
    in_window = compiles.n - compiled_before
    tr = None
    if trace:
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        tr = trace_mod.read(files[0])
        shutil.rmtree(tdir, ignore_errors=True)
    stats = {k: v - stats0.get(k, 0) for k, v in plat.session.stats.items()
             if isinstance(v, int)}
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    plat.close()
    del plat
    gc.collect()

    t_check = time.perf_counter()
    ok, counts = check.check(dep, log, seed)
    t_check = time.perf_counter() - t_check
    run = RunData(cell, dep, mix, log, log.latencies(),
                  list(dict.fromkeys(dep.tags + [f.tag for f in
                                                 dep.functions.values()])),
                  dev.device_kind, tr, obs, stats)
    group = "per_layer" if trace else "end_to_end"
    values = {}
    for m in cell_metrics(spec, cell, group):
        if m["name"] == "setup_s":
            v = setup_s
        elif m["name"] in E2E_READERS:
            v = E2E_READERS[m["name"]](run)
        else:
            v = metrics.reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": ok, "attempted": log.attempted(),
              "failed": log.undecided(), "metrics": values, "device": device}
    if tr is not None and tr.devices:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = trace_mod.breakdown(tr)
    result["checks"] = {k: {"value": counts[k], "limit": lim}
                        for k, lim in check.LIMITS.items()}
    kinds = collections.Counter(k for _a, _w, k in check.answers(log))
    sizes = [n for _s, _e, n in log.calls]
    result["_stderr"] = [
        f"cell {cell['name']}: {len(dep.workers)} workers, "
        f"{len(dep.functions)} functions, {len(dep.preload)} pre-existing "
        f"activations; seed {seed}; {dev.platform} {dev.device_kind}",
        f"set-up {setup_s:.3f} s: warm-up {warm}, {compiles.n - in_window} "
        f"programs compiled or loaded ({compiles.seconds:.3f} s)",
        f"window {log.t_stop:.3f} s: {len(log.calls)} calls, arrivals per "
        f"call mean {np.mean(sizes) if sizes else 0:.2f} max "
        f"{max(sizes) if sizes else 0}; {log.attempted()} due, "
        f"{len(log.decided_at)} decided, {log.undecided()} undecided; "
        f"programs compiled or loaded in the window {in_window}",
        f"session counters over the window {stats}; generator lateness "
        f"samples {len(log.gen_late)}; reference replay {t_check:.3f} s",
        "latency ms p50 {:.3f} p95 {:.3f} p99 {:.3f} over {} arrivals".format(
            *(np.percentile(run.lat, [50, 95, 99]) * 1e3), len(run.lat)),
        f"start kinds {dict(kinds)}; unplaced share "
        f"{counts['unplaced'] / max(counts['decisions'], 1):.4f}",
        f"metrics {json.dumps(values)}",
    ] + [f"check {line}" for line in check.report_lines(counts)]
    return result
