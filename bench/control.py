#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's and the
control's, on many seeds, at the cell's own size and load.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--seconds 10]
        [--faults answer-altered,half-left-out,state-unchanged]
        [--fault-seconds 10]

For each seed, in one process: the cell's deployment and window as
``bench/run.py`` runs them, then the comparison of the program's decisions
with the plain reference (the lower readings), and of each control's -- the
reference with a stated guarantee broken or its memory accounting in a
lower precision (``reference.CONTROLS``), put in the program's place on the
same log -- with the reference (the upper readings).  Each fault named
(``bench/faults.py``) then gets a window of its own, at the same size, with
the fault planted in the timed path.  One JSON line per seed.  Benchmark
runs never run the controls or the faults.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, faults, harness, reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seconds", type=float, default=None)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT / ".jax_cache")
    spec = harness.load_benchmark(ROOT)
    cell = harness.find_cell(spec, args.workload)
    seconds = args.seconds or spec["run_seconds"]
    planted = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        stand = harness.stand_up(cell, seed, seconds)
        log = harness.drive(stand)
        stand.plat.close()
        del stand.plat
        t = time.perf_counter()
        ok, prog = check.check(stand.dep, log, seed)
        t_ref = time.perf_counter() - t
        ctl = {c: check.control_reading(stand.dep, log, seed, c)
               for c in reference.CONTROLS}
        broken = {}
        for name in planted:
            st = harness.stand_up(cell, seed, args.fault_seconds or seconds)
            faults.plant(st.plat, name)
            flog = harness.drive(st)
            st.plat.close()
            del st.plat
            fok, counts = check.check(st.dep, flog, seed)
            broken[name] = dict(counts, correct=fok)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "correct": ok, "program": prog, "control": ctl,
                          "faults": broken, "reference_s": t_ref,
                          "decided": len(log.decided_at)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
