#!/usr/bin/env python3
"""The program's own spans on the device trace's clock.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> [--out <file.xplane.pb>]

One traced window of a cell, as ``bench/run.py --trace 1`` makes it, except
that the program's stage timers (``repro.obs.StageTimers``, one sample in
one) also write each span into the profiler's trace as ``sched.stage.<stage>``
(``jax.profiler.TraceAnnotation``), and every garbage collection in the
window becomes a ``bench.gc`` span.  It prints one JSON line: the idle gaps
of the device cut at span boundaries and put down to the innermost span,
the self time of each stage from the trace beside the program's own
histograms, the per-layer readers of ``bench/stages.py``, and the checks
that the spans nest and that every decision agrees with the reference.
``--out`` keeps the trace, and the registry snapshot and session counters
beside it (``<file>.json``).  Like ``bench/run.py``, it refuses any device
but a TPU.

The reduction reads the host events named ``sched.stage.*`` and ``bench.*``,
each with the index of its thread's line; the device operations, the window
and the ``bench.*`` spans come from ``bench/trace.py``.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import gc
import glob
import json
import os
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, harness, loop, metrics, stages  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

#: (name, start s, end s, thread): a host span on the trace's clock
Span = Tuple[str, float, float, int]
STAGE = stages.PREFIX + "."


def read(path) -> List[Span]:
    """The host spans of one ``.xplane.pb`` that the program
    (``sched.stage.*``) or the benchmark (``bench.*``) wrote."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith((STAGE, "bench.")):
                    s = ev.start_ns * 1e-9
                    out.append((ev.name, s, s + ev.duration_ns * 1e-9, k))
    return out


def _idle(tr: trace_mod.Trace, device: int) -> List[Tuple[float, float]]:
    lo, hi = tr.window
    busy = sorted((max(s, lo), min(e, hi)) for _n, s, e, d in tr.ops
                  if d == device and min(e, hi) > max(s, lo))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _innermost(spans: List[Span], lo: float, hi: float):
    """``[lo, hi)`` cut at span boundaries: ``(start, end, label)`` pieces
    in order, each labelled by the innermost span covering it."""
    marks = []
    for i, (n, s, e, _t) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if n != "bench.window" and e > s:
            marks += [(s, 1, -e, i), (e, 0, 0, i)]
    marks.sort()  # ends before starts; of two starts, the outer first
    out, stack, t = [], [], lo
    for x, start, _e, i in marks:
        if x > t:
            out.append((t, x, spans[stack[-1]][0] if stack else "other"))
            t = x
        if start:
            stack.append(i)
        else:
            stack.remove(i)
    if hi > t:
        out.append((t, hi, "other"))
    return out


def idle_pieces(tr: trace_mod.Trace, spans: List[Span],
                device: int = 0) -> List[Tuple[str, float]]:
    """Each idle stretch of ``device`` in the window cut at span boundaries,
    each piece labelled by the innermost span covering it (``other`` where
    none does); the pieces sum to the window less the busy time."""
    gaps = _idle(tr, device)
    out = []
    k = 0
    for s, e, label in _innermost(spans, *tr.window):
        while k < len(gaps) and gaps[k][1] <= s:
            k += 1
        j = k
        while j < len(gaps) and gaps[j][0] < e:
            a, b = max(s, gaps[j][0]), min(e, gaps[j][1])
            if b > a:
                out.append((label, b - a))
            j += 1
    return out


def _nested(spans: List[Span]):
    """Per thread, the spans in start order, each with its parent's index
    (``-1`` at the top), and the number of pairs that overlap without one
    containing the other."""
    by_thread: Dict[int, List[Span]] = collections.defaultdict(list)
    for sp in spans:
        by_thread[sp[3]].append(sp)
    order, parent, faults = [], [], 0
    for t in sorted(by_thread):
        stack: List[int] = []
        for sp in sorted(by_thread[t], key=lambda x: (x[1], -x[2])):
            while stack and order[stack[-1]][2] <= sp[1]:
                stack.pop()
            if stack and order[stack[-1]][2] < sp[2]:
                faults += 1
            parent.append(stack[-1] if stack else -1)
            stack.append(len(order))
            order.append(sp)
    return order, parent, faults


def nesting_faults(spans: List[Span]) -> int:
    """Pairs of spans of one thread that overlap without one containing
    the other: 0 where every span nests."""
    return _nested(spans)[2]


def self_seconds(spans: List[Span], name: str) -> float:
    """Summed self time of the spans called ``name``: each one's duration
    less what its child spans cover."""
    order, parent, _f = _nested(spans)
    own = [e - s for _n, s, e, _t in order]
    for i, p in enumerate(parent):
        if p >= 0:
            own[p] -= order[i][2] - order[i][1]
    return sum(x for x, sp in zip(own, order) if sp[0] == name)


def device_lead(tr: trace_mod.Trace, spans: List[Span],
                kernel: str = "affinity_valid_kernel") -> float:
    """How far, at least, the trace puts the device's events ahead of the
    host spans that caused them: the largest lead of the k-th ``kernel``
    event over the start of the k-th ``valid_launch`` span, within which
    its inputs are shipped (0 where none leads).  The profiler aligns the
    two clocks only this well."""
    launches = sorted(s for n, s, _e, _t in spans
                      if n == STAGE + "valid_launch")
    events = sorted(s for n, s, _e, _d in tr.ops if n == kernel)
    return max([0.0] + [a - k for a, k in zip(launches, events)])


def report(tr: trace_mod.Trace, spans: List[Span], run) -> dict:
    """What one traced window with program spans shows."""
    lo, hi = tr.window
    inside = [sp for sp in spans if lo <= sp[1] and sp[2] <= hi]
    by_label: Dict[str, float] = collections.defaultdict(float)
    for label, s in idle_pieces(tr, inside):
        by_label[label] += s
    # the idle time under bench.decide_batch spans, and the part of it put
    # down to a program span
    calls = sorted((s, e) for n, s, e, _t in inside
                   if n == "bench.decide_batch")
    starts = [s for s, _e in calls]

    def in_call(sp):
        k = bisect.bisect_right(starts, sp[1]) - 1
        return k >= 0 and sp[2] <= calls[k][1]

    pieces = idle_pieces(tr, [sp for sp in inside if in_call(sp)])
    in_calls = sum(s for label, s in pieces if label != "other")
    in_calls_program = sum(s for label, s in pieces
                           if label.startswith(STAGE))
    names = sorted({n for n, *_ in inside if n.startswith(STAGE)})
    count = collections.Counter(n for n, *_ in inside)
    mean_ms = {n[len(STAGE):]: 1e3 * sum(e - s for m, s, e, _t in inside
                                         if m == n) / count[n]
               for n in names}
    self_ms = {n[len(STAGE):]: 1e3 * self_seconds(inside, n) / count[n]
               for n in names}
    values = {m: metrics.reader(m)(run) for m in
              ("route_ms", "valid_pad_ms", "valid_launch_ms",
               "valid_fetch_ms", "valid_h2d_kb", "item_self_ms",
               "route_self_ms", "commit_ms",
               "affinity_valid_kernel_roofline")}
    return {
        "window_s": tr.window_s, "busy_s": tr.busy_s(),
        "idle_gaps": sorted(([n, s] for n, s in by_label.items()),
                            key=lambda x: -x[1]),
        "idle_in_calls_s": in_calls,
        "idle_in_calls_program_s": in_calls_program,
        "span_counts": {n: count[n] for n in sorted(count)},
        "span_mean_ms": mean_ms, "span_self_ms": self_ms,
        "nesting_faults": nesting_faults(inside),
        "kernel_events": len(tr.kernel_events("affinity_valid_kernel")),
        "device_lead_ms": 1e3 * device_lead(tr, inside),
        "metrics": values,
    }


@contextlib.contextmanager
def gc_spans(annotate):
    """Every garbage collection while open becomes a ``bench.gc`` span."""
    open_ = []

    def on_gc(phase, _info):
        if phase == "start":
            open_.append(annotate("bench.gc"))
            open_[-1].__enter__()
        elif open_:
            open_.pop().__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)


def window(stand: harness.Stand, annotate, rng: random.Random):
    """The cell's open-loop window with the program's stage timers on at
    one sample in one, writing their spans through ``annotate`` (``None``:
    histograms only).  Returns the window's log and the ``Obs`` bundle."""
    from repro.obs import Obs, StageTimers

    obs = Obs()
    obs.timers = StageTimers(obs.registry, sample=1, annotate=annotate)
    stand.plat.attach_obs(obs)
    span = annotate or (lambda name: contextlib.nullcontext())
    with span("bench.window"):
        log = loop.run(stand.plat, stand.dep, stand.roots,
                       seconds=stand.seconds, max_wave=stand.mix["max_wave"],
                       rng=rng, clock=stand.clock, annotate=annotate)
    return log, obs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="keep the trace here (.xplane.pb)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    spec = harness.load_benchmark(ROOT)
    cell = harness.find_cell(spec, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("spans: needs a TPU; not falling back", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT / ".jax_cache")
    stand = harness.stand_up(cell, args.seed, args.seconds)
    stats0 = dict(stand.plat.session.stats)
    tdir = tempfile.mkdtemp(prefix="bench-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    annotate = jax.profiler.TraceAnnotation
    gc.collect()
    gc.freeze()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with gc_spans(annotate):
        log, obs = window(stand, annotate, random.Random(args.seed))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    tr, spans = trace_mod.read(path), read(path)
    stats = {k: v - stats0.get(k, 0)
             for k, v in stand.plat.session.stats.items()
             if isinstance(v, int)}
    snap = obs.registry.snapshot()
    if args.out:
        shutil.copy(path, args.out)
        Path(args.out + ".json").write_text(json.dumps(
            {"stats": stats, "snapshot": snap}, indent=1) + "\n")
    shutil.rmtree(tdir, ignore_errors=True)
    dep = stand.dep
    run = harness.RunData(cell, dep, stand.mix, log, log.latencies(),
                          list(dict.fromkeys(dep.tags + [
                              f.tag for f in dep.functions.values()])),
                          jax.devices()[0].device_kind, tr, obs, stats)
    out = report(tr, spans, run)
    stand.plat.close()
    t = time.perf_counter()
    ok, counts = check.check(dep, log, args.seed)
    out.update(correct=ok, check_s=time.perf_counter() - t, stats=stats,
               placed=sum(1 for ev in log.events if ev[0] == "call"
                          for w, _k in ev[4] if w is not None),
               mismatches={k: counts[k] for k in check.LIMITS})
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
