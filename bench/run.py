#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  One run builds the deployment from the
seed, stands the platform up on the TPU (``backend="pallas"``), warms every
shape the cell's traffic can reach, drives the open loop for ``--seconds``
(``bench/loop.py``), then replays the window against the plain reference
(``bench/check.py``) and prints one JSON line.  With ``--trace 1`` the window
is profiled and the line carries the cell's per-layer metrics
(``bench/metrics/<name>.py``) instead of its end-to-end ones.

It refuses any device but a TPU, and fewer chips than the cell asks for,
and then prints no result.  JAX's persistent compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/`` at
the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_benchmark(ROOT)
    cell = harness.find_cell(spec, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind}); not falling back",
              file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT / ".jax_cache")
    result = harness.run_cell(spec, cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START)
    for line in result.pop("_stderr"):
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
