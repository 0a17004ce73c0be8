"""The one arrival generator.  A traffic mix is a data file,
``bench/traffic/<name>.json``, of parameters this module reads:

* ``process``: ``"poisson"`` (open-loop arrivals at ``rate_per_s``);
* ``functions``: ``"popularity"`` (the configuration's popularity weights)
  or a list of ``{"function": name, "share": weight}``; a name may carry
  ``{origin}``, filled with the arrival's origin zone;
* ``origins`` (optional): ``{zone: weight}`` origin zones of the roots;
* ``max_wave``: the most arrivals the controller hands over in one call.

Arrival times and the function sequence are drawn from the seed, but as the
same set in another order: the gaps are the Poisson process's fixed
quantiles, shuffled, and each function appears its popularity's share of
times, shuffled.  Functions that spawn children (the configuration's
``spawns``) produce them when they are placed.
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Optional, Sequence

import numpy as np

from .deploy import BENCH, Deployment, _stream


@dataclasses.dataclass
class Arrival:
    id: int
    due: float  # seconds after the window opens
    function: str
    origin: Optional[str]  # origin zone; None on a flat cluster
    parent: int = -1


def load_mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _apportion(n: int, weights: Sequence[float]) -> np.ndarray:
    """Largest-remainder split of ``n`` by ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    rest = n - int(out.sum())
    if rest:
        out[np.argsort(-(exact - out), kind="stable")[:rest]] += 1
    return out


def roots(dep: Deployment, mix: dict, seed: int, seconds: float
          ) -> List[Arrival]:
    """The arrivals due in ``[0, seconds)``, in due order."""
    if mix["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['process']!r}")
    rng = _stream(seed, 3)
    n = int(round(mix["rate_per_s"] * seconds))
    if n == 0:
        return []
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)  # Exp(1) quantiles
    gaps = gaps[rng.permutation(n)]
    due = (np.cumsum(gaps) - gaps / 2) * (seconds / gaps.sum())
    if mix["functions"] == "popularity":
        names = [f for f, _w in dep.popularity]
        weights = [w for _f, w in dep.popularity]
    else:
        names = [e["function"] for e in mix["functions"]]
        weights = [e["share"] for e in mix["functions"]]
    zs = list(mix.get("origins") or {None: 1.0})
    zw = [mix["origins"][z] for z in zs] if mix.get("origins") else [1.0]
    # every (function, origin) pair its joint share of the n arrivals
    pairs = [(f, z) for f in range(len(names)) for z in range(len(zs))]
    seq = np.repeat(np.arange(len(pairs)),
                    _apportion(n, [weights[f] * zw[z] for f, z in pairs]))
    seq = seq[rng.permutation(n)]
    out = []
    for i in range(n):
        f, z = pairs[seq[i]]
        o = zs[z]
        out.append(Arrival(i, float(due[i]),
                           names[f].format(origin=o) if o else names[f], o))
    return out


def children(dep: Deployment, parent: Arrival, placed_at: float,
             zone: Optional[str], next_id: int) -> List[Arrival]:
    """What a placed ``parent`` invokes: due ``delay`` after it was placed,
    from the zone it runs in."""
    out = []
    for f, count, delay in dep.functions[parent.function].spawns:
        for _ in range(count):
            out.append(Arrival(next_id, placed_at + delay, f, zone or None,
                               parent.id))
            next_id += 1
    return out
