#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate the platform sustains
without a growing backlog, by a sweep on the chip.

    python3 bench/sweep.py --workload <name> --rates 400,800,1200 \
        [--seconds 6] [--seed 1]

One process builds the cell's deployment and warms it once, then drives
one open-loop window per rate, each after the previous window's activations
have completed.  Per rate it prints the decisions offered and completed per
second, the latency median and 95th percentile, the 95th percentile of the
window's first and last thirds (a backlog that grows shows as a rising
tail) and what was still undecided at the end.  The rate a cell runs at is
then written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import harness, loop, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT / ".jax_cache")
    spec = harness.load_benchmark(ROOT)
    cell = harness.find_cell(spec, args.workload)
    t = time.perf_counter()
    stand = harness.stand_up(cell, args.seed, args.seconds)
    dep, mix, clock, plat = stand.dep, stand.mix, stand.clock, stand.plat
    print(f"sweep {cell['name']}: set-up {time.perf_counter() - t:.1f} s",
          flush=True)
    t_base = 0.0
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        stats0 = dict(plat.session.stats)
        roots = traffic.roots(dep, m, args.seed + k, args.seconds)
        log = loop.run(plat, dep, roots, seconds=args.seconds,
                       max_wave=m["max_wave"], rng=random.Random(k),
                       clock=_Offset(clock, t_base))
        t_base += log.t_stop + 1.0
        clock.now = t_base
        for d in log.running:
            plat.complete(d)
        lat = np.asarray(log.latencies()) * 1e3
        due = np.asarray([a.due for a in log.arrivals.values()
                          if a.due < args.seconds])
        third = args.seconds / 3
        sizes = [n for _s, _e, n in log.calls]
        print(json.dumps({
            "rate": rate,
            "offered_per_s": log.attempted() / args.seconds,
            "decided_per_s": len(log.decided_at) / log.t_stop,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_third_ms": float(np.percentile(lat[due < third], 95)),
            "p95_last_third_ms": float(np.percentile(
                lat[due >= 2 * third], 95)),
            "undecided": log.undecided(),
            "wave_mean": float(np.mean(sizes)), "wave_max": max(sizes),
            "stats": {k2: v - stats0.get(k2, 0)
                      for k2, v in plat.session.stats.items()}}),
            flush=True)
    plat.close()
    return 0


class _Offset:
    """Window clock shifted past earlier windows, so one platform's pool
    sees time run forward across the sweep."""

    def __init__(self, clock, base):
        self.clock, self.base = clock, base

    @property
    def now(self):
        return self.clock.now - self.base

    @now.setter
    def now(self, v):
        self.clock.now = v + self.base


if __name__ == "__main__":
    sys.exit(main())
