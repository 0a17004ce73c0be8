"""Operations and bytes of each kernel, from its logical shapes.

One module per kernel, named as the kernel's device events are
(``bench.trace.op_name``), each with ``ops_bytes(R, W, T) -> (ops, bytes)``:
the work any implementation of that step must do, counted from the
unpadded shapes.  Padding, outputs the caller does not need and dtype
choices stay out, so a program that drops them cannot push a share
over 100%.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def bound_s(kernel: str, R: int, W: int, T: int, peak: Dict[str, float]
            ) -> Tuple[float, str]:
    """The least time the chip could take for one call, and which roof
    bounds it (``compute`` or ``memory``)."""
    ops, nbytes = importlib.import_module(
        f"bench.roofline.{kernel}").ops_bytes(R, W, T)
    tc, tm = ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
