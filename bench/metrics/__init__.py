"""One file per per-layer metric, named as in ``BENCHMARK.json``; each
defines ``read(run) -> float | None``."""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def reader(name: str):
    """The ``read`` function of metric ``name``."""
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} ({path.name})")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics._" + name.replace(".", "__"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def names():
    return sorted(p.stem for p in HERE.glob("*.py") if p.stem != "__init__")
