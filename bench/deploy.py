"""A configuration file turned into a deployment, deterministically from a seed.

The configuration (``bench/configs/<name>.json``) states the cluster, the
function population, the aAPP policies in structured form, the pre-existing
load and the warm pool.  :func:`build` draws everything that is random from
the seed; :func:`platform` stands the system under test up on it, and the
plain reference (:mod:`bench.reference`) reads the same :class:`Deployment`
without touching the program.

Sizes are drawn as fixed quantiles of their distribution and only their
assignment is shuffled by the seed, so every seed runs the same set of
sizes in another order.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Function:
    name: str
    memory: int  # MB
    tag: str
    duration: float  # seconds every invocation runs
    spawns: Tuple[Tuple[str, int, float], ...] = ()  # (function, count, delay s)


@dataclasses.dataclass
class Deployment:
    name: str
    seed: int
    workers: List[str]
    zone: List[str]  # "" for a flat cluster
    wclass: List[str]
    memory: np.ndarray  # [W] int64 MB
    functions: Dict[str, Function]
    popularity: List[Tuple[str, float]]  # (function, weight), for mixes that ask
    tags: List[str]
    policies: List[dict]
    preload: List[Tuple[str, int]]  # (function, worker index) in order
    keep_alive: float
    hot_window: float

    @property
    def zones(self) -> List[str]:
        return list(dict.fromkeys(z for z in self.zone if z))

    def select(self, selector) -> List[int]:
        """Worker indices a block's ``workers`` entry names, in order."""
        if selector == "*":
            return list(range(len(self.workers)))
        if isinstance(selector, dict):
            return [j for j in range(len(self.workers))
                    if self.zone[j] == selector.get("zone", self.zone[j])
                    and self.wclass[j] == selector.get("class", self.wclass[j])]
        index = {w: j for j, w in enumerate(self.workers)}
        return [index[w] for w in selector]

    def chain(self, tag: str) -> List[dict]:
        """Listing 1's block list for ``tag``: its blocks, then the default
        policy's unless ``followup: fail``; an absent default policy is one
        wildcard best_first block."""
        pols = {p["tag"]: p for p in self.policies}
        default = pols.get("default", {"blocks": [{"workers": "*"}],
                                       "followup": "fail"})
        p = pols.get(tag)
        if p is None:
            return list(default["blocks"])
        out = list(p["blocks"])
        if p.get("followup", "default") != "fail" and tag != "default":
            out += list(default["blocks"])
        return out

    def rows(self, function: str) -> int:
        return len(self.chain(self.functions[function].tag))


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    return json.loads(path.read_text())


def _quantiles(n: int, spec: dict) -> np.ndarray:
    """``n`` quantiles at (k + 1/2) / n of ``spec``'s distribution:
    ``lognormal`` (``mu`` and ``sigma`` of the logarithm) or ``burr``
    (Burr type XII with shapes ``c``, ``k`` and ``scale``), clipped to
    ``min`` / ``max`` where given."""
    p = (np.arange(n) + 0.5) / n
    kind = spec["distribution"]
    if kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(q) for q in p])
        q = np.exp(spec["mu"] + spec["sigma"] * z)
    elif kind == "burr":
        q = spec["scale"] * ((1 - p) ** (-1 / spec["k"]) - 1) ** (
            1 / spec["c"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(q, spec.get("min", -np.inf), spec.get("max", np.inf))


def _stream(seed: int, k: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), k])


def build(cfg: dict, seed: int) -> Deployment:
    workers, zone, wclass, mem = [], [], [], []
    for z in cfg["zones"]:
        for _r in range(z.get("replicate", 1)):
            for g in z["groups"]:
                for _k in range(g["count"]):
                    workers.append(f"{z['name'] or 'w'}{len(workers):05d}")
                    zone.append(z["name"])
                    wclass.append(g["class"])
                    mem.append(int(g["memory_mb"]))
    tags = [p["tag"] for p in cfg["policies"]]
    fcfg = cfg["functions"]
    functions: Dict[str, Function] = {}
    popularity: List[Tuple[str, float]] = []
    if "list" in fcfg:
        for f in fcfg["list"]:
            functions[f["name"]] = Function(
                f["name"], int(f["memory_mb"]), f["tag"], float(f["duration_s"]),
                tuple((s["function"], int(s["count"]), float(s["delay_s"]))
                      for s in f.get("spawns", ())))
    else:
        n, ntag = fcfg["count"], fcfg["tags"]
        m, d = fcfg["memory_mb"], fcfg["duration_s"]
        rng = _stream(seed, 1)
        mems = np.maximum(np.rint(_quantiles(n, m)), 1).astype(
            np.int64)[rng.permutation(n)]
        durs = _quantiles(n, d)[rng.permutation(n)]
        for i in range(n):
            functions[f"f{i:04d}"] = Function(
                f"f{i:04d}", int(mems[i]), tags[i % ntag], float(durs[i]))
        ranks = rng.permutation(n)  # function i has popularity rank ranks[i]
        s = fcfg["popularity_zipf_s"]
        popularity = [(f"f{i:04d}", 1.0 / (ranks[i] + 1) ** s)
                      for i in range(n)]
    dep = Deployment(cfg["name"], seed, workers, zone, wclass,
                     np.asarray(mem, np.int64), functions, popularity, tags,
                     cfg["policies"], [], float(cfg["pool"]["keep_alive_s"]),
                     float(cfg["pool"]["hot_window_s"]))
    dep.preload = _preload(dep, cfg["preload"], _stream(seed, 2))
    return dep


def _preload(dep: Deployment, rules: List[dict], rng) -> List[Tuple[str, int]]:
    """Pre-existing activations: each worker of a rule's class is filled
    towards a share of its memory (shares spread evenly over 0..fill_max,
    shuffled) from ``picks`` uniform draws of the rule's functions, keeping
    each draw that still fits under the target."""
    out = []
    for rule in rules:
        idx = [j for j in range(len(dep.workers))
               if dep.wclass[j] == rule["class"]]
        share = (np.arange(len(idx)) + 0.5) / len(idx) * rule["fill_max"]
        share = share[rng.permutation(len(idx))]
        picks = rng.integers(0, 1 << 30, (len(idx), rule["picks"]))
        for k, j in enumerate(idx):
            names = (list(dep.functions) if rule["functions"] == "all"
                     else [f.format(zone=dep.zone[j])
                           for f in rule["functions"]])
            target = share[k] * dep.memory[j]
            used = 0
            for p in picks[k]:
                f = names[int(p) % len(names)]
                m = dep.functions[f].memory
                if used + m <= target:
                    used += m
                    out.append((f, j))
    return out


def script_text(dep: Deployment) -> str:
    """The aAPP source the program compiles, rendered from the structured
    policies."""
    def block(b: dict, lead: str, ind: str) -> str:
        w = b["workers"]
        if w == "*":
            ws = "*"
        else:
            ws = "[" + ", ".join(dep.workers[j] for j in dep.select(w)) + "]"
        out = [f"{lead}workers: {ws}"]
        if "strategy" in b:
            out.append(f"{ind}strategy: {b['strategy']}")
        if "topology" in b:
            out.append(f"{ind}topology: {b['topology']}")
        terms = list(b.get("affinity", ())) + [
            f"!{t}" for t in b.get("anti_affinity", ())]
        if terms:
            out.append(f"{ind}affinity: [{', '.join(terms)}]")
        rules = []
        if "capacity_used" in b:
            rules.append(f"capacity_used {b['capacity_used']}%")
        if "max_concurrent_invocations" in b:
            rules.append("max_concurrent_invocations "
                         f"{b['max_concurrent_invocations']}")
        if rules:
            out.append(f"{ind}invalidate:")
            out += [f"{ind}  - {r}" for r in rules]
        return "\n".join(out) + "\n"

    text = []
    for p in dep.policies:
        text.append(f"{p['tag']}:\n")
        followup = p.get("followup", "default")
        if len(p["blocks"]) == 1 and followup == "default":
            text.append(block(p["blocks"][0], "  ", "  "))
            continue
        for b in p["blocks"]:
            text.append(block(b, "  - ", "    "))
        if followup != "default":
            text.append(f"  - followup: {followup}\n")
    return "".join(text)


def platform(dep: Deployment, clock, *, backend: str = "pallas",
             interpret: bool = False, obs=None):
    """The system under test on this deployment: ``Platform`` over a cluster
    state holding the pre-existing activations, a fixed-TTL warm pool, and
    the window's clock."""
    from repro.core.state import ClusterState, Registry
    from repro.platform import Platform
    from repro.pool import StartCosts, WarmPool, make_policy

    state = ClusterState()
    for j, w in enumerate(dep.workers):
        state.add_worker(w, max_memory=float(dep.memory[j]),
                         zone=dep.zone[j] or None)
    reg = Registry({f.name: (float(f.memory), f.tag)
                    for f in dep.functions.values()})
    for f, j in dep.preload:
        state.allocate(f, dep.workers[j], reg)
    pool = WarmPool(make_policy("fixed_ttl", ttl=dep.keep_alive),
                    costs=StartCosts(), hot_window=dep.hot_window)
    return Platform.from_yaml(script_text(dep), cluster=state, registry=reg,
                              pool=pool, clock=clock, backend=backend,
                              interpret=interpret, obs=obs,
                              seed=int(dep.seed) & 0x7FFFFFFF)


def run_limit_ok(dep: Deployment, seconds: float) -> bool:
    """A container is parked when its activation completes inside the run,
    so none idles longer than the run: within a run shorter than the
    keep-alive the pool never expires one, which the reference relies on."""
    return seconds < dep.keep_alive
