"""The plain reference: Listing 1 of the aAPP paper, its zone router and the
warm pool, written out straight from their stated semantics.  It imports
nothing of the program and takes nothing the program made: it rebuilds the
deployment from the configuration and the seed, and replays the window's
log (what the platform was asked, when, and which activations completed)
with its own decisions.

Semantics, per decision of function ``f`` with memory ``m`` at time ``now``:

* the chain is the blocks of ``f``'s tag, then the default policy's
  (``deploy.Deployment.chain``); a block's candidates are its workers in
  order (``*``: the cluster's order);
* a worker is valid iff ``used + m <= max``, ``used * 100 < cap * max``
  for a ``capacity_used cap%`` rule, fewer than ``n`` instances run on it
  for a ``max_concurrent_invocations n`` rule, every affine tag runs on it
  and no anti-affine tag does (integer MB: no rounding anywhere);
* the first block with a valid worker decides, by its strategy over the
  valid candidates in order, first on ties: ``best_first`` and ``random``
  first narrow to the highest warmth rank present, then take the first or
  ``rng.choice``; ``least_loaded`` the fewest running instances;
  ``warmest`` the highest rank, then the fewest instances; ``min_cost``
  the least ``(10, 2, 0)[rank] + instances`` -- the cost
  ``(0.5, 0.1, 0.0)[rank] + 0.05 * instances`` scaled by 20, exact;
* a tag whose chain carries a ``topology`` hint is routed: block by block,
  zone by zone (the origin zone first under ``local_first``, then the
  cluster's zone order), over the block's workers in that zone;
* warmth rank of ``(f, w)``: 0 with no idle container of ``f`` on ``w``,
  else 2 if the oldest idle one went idle at most ``hot_window`` ago, else
  1.  Placing takes that oldest idle container (hot or warm start) or
  starts a cold one; completing parks the container, idle from ``now``.
  No container outlives the keep-alive within one run.

``control`` names what to break, for the control the comparison has to
fail: ``"no-anti-affinity"`` drops every anti-affinity term;
``"bf16-memory"`` keeps the memory accounting (each worker's used MB, the
fit and the ``capacity_used`` test) in bfloat16, rounded to nearest even
after every operation, in place of exact integers.
"""
from __future__ import annotations

import collections
import random
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import deploy
from .deploy import Deployment

LIFE20 = (10, 2, 0)  # start cost by warmth rank in units of one instance
CONTROLS = ("no-anti-affinity", "bf16-memory")


def _bf16(x) -> np.ndarray:
    """``x`` rounded to the nearest bfloat16 (ties to even), as float32."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


class _Block:
    def __init__(self, dep: Deployment, b: dict, tcol: Dict[str, int],
                 control: Optional[str]):
        self.cand = np.asarray(dep.select(b["workers"]), np.int64)
        self.strategy = {"any": "random", "best-first": "best_first",
                         "platform": "best_first",
                         "least-loaded": "least_loaded",
                         "min-cost": "min_cost"}.get(
            b.get("strategy", "best_first"), b.get("strategy", "best_first"))
        self.affine = [tcol[t] for t in b.get("affinity", ())]
        self.anti = ([] if control == "no-anti-affinity"
                     else [tcol[t] for t in b.get("anti_affinity", ())])
        self.cap = b.get("capacity_used")
        self.conc = b.get("max_concurrent_invocations")
        self.topology = b.get("topology")
        zone = np.asarray(dep.zone)
        self.by_zone = {z: self.cand[zone[self.cand] == z] for z in dep.zones}


class Reference:
    def __init__(self, dep: Deployment, rng: random.Random,
                 control: Optional[str] = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.dep = dep
        self.rng = rng
        self.bf16 = control == "bf16-memory"
        tags = list(dict.fromkeys(
            list(dep.tags) + [f.tag for f in dep.functions.values()]))
        self.tcol = {t: i for i, t in enumerate(tags)}
        W = len(dep.workers)
        self.max_mem = dep.memory.astype(np.int64)
        self.used = np.zeros(W, np.float32 if self.bf16 else np.int64)
        self.load = np.zeros(W, np.int64)
        self.occ = np.zeros((W, len(tags)), np.int32)
        self.zones = dep.zones
        self.chains: Dict[str, List[_Block]] = {}
        for t in set(tags):
            self.chains[t] = [_Block(dep, b, self.tcol, control)
                              for b in dep.chain(t)]
        # (worker, function) -> idle-since times of idle containers, oldest
        # first; function -> workers holding any
        self.idle: Dict[Tuple[int, str], collections.deque] = {}
        self.idle_by_fn: Dict[str, set] = collections.defaultdict(set)
        self.running: Dict[int, Tuple[int, str]] = {}  # arrival -> (w, f)
        for f, j in dep.preload:
            self._alloc(f, j)

    # ---- state ------------------------------------------------------- #

    def _alloc(self, f: str, j: int) -> None:
        fn = self.dep.functions[f]
        self.used[j] += fn.memory
        if self.bf16:
            self.used[j] = _bf16(self.used[j])
        self.load[j] += 1
        self.occ[j, self.tcol[fn.tag]] += 1

    def _release(self, f: str, j: int) -> None:
        fn = self.dep.functions[f]
        self.used[j] -= fn.memory
        if self.bf16:
            self.used[j] = _bf16(self.used[j])
        self.load[j] -= 1
        self.occ[j, self.tcol[fn.tag]] -= 1

    def _rank(self, f: str, j: int, now: float) -> int:
        q = self.idle.get((j, f))
        if not q:
            return 0
        return 2 if max(0.0, now - q[0]) <= self.dep.hot_window else 1

    # ---- one decision ------------------------------------------------ #

    def _pick(self, f: str, b: _Block, cand: np.ndarray, now: float
              ) -> Optional[int]:
        if cand.size == 0:
            return None
        m = self.dep.functions[f].memory
        used, mx = self.used[cand], self.max_mem[cand]
        if self.bf16:
            mx = _bf16(mx)
            ok = _bf16(used + _bf16(m)) <= mx
            if b.cap is not None:
                ok &= _bf16(used * 100) < _bf16(b.cap * mx)
        else:
            ok = used + m <= mx
            if b.cap is not None:
                ok &= used * 100 < b.cap * mx
        if b.conc is not None:
            ok &= self.load[cand] < b.conc
        for c in b.affine:
            ok &= self.occ[cand, c] > 0
        for c in b.anti:
            ok &= self.occ[cand, c] == 0
        cand = cand[ok]
        if cand.size == 0:
            return None
        holders = self.idle_by_fn.get(f)
        if holders:
            full = np.zeros(len(self.used), np.int64)
            for j in holders:
                full[j] = self._rank(f, j, now)
            rank = full[cand]
        else:
            rank = np.zeros(cand.size, np.int64)
        s = b.strategy
        if s in ("best_first", "random"):
            top = rank.max()
            if top > 0:
                cand = cand[rank == top]
            if s == "best_first":
                return int(cand[0])
            return int(self.rng.choice(cand))
        load = self.load[cand]
        if s == "least_loaded":
            key = load
        elif s == "warmest":
            key = (2 - rank) * (1 << 40) + load
        elif s == "min_cost":
            key = np.asarray(LIFE20)[rank] + load
        else:
            raise ValueError(f"strategy {s!r} has no reference")
        return int(cand[int(np.argmin(key))])

    def decide(self, f: str, origin: Optional[str], now: float
               ) -> Optional[int]:
        chain = self.chains[self.dep.functions[f].tag]
        hint = next((b.topology for b in chain if b.topology), None)
        if hint is None or len(self.zones) <= 1:
            for b in chain:
                j = self._pick(f, b, b.cand, now)
                if j is not None:
                    return j
            return None
        if hint != "local_first":
            raise ValueError(f"zone strategy {hint!r} has no reference")
        order = ([origin] + [z for z in self.zones if z != origin]
                 if origin in self.zones else list(self.zones))
        for b in chain:
            for z in order:
                j = self._pick(f, b, b.by_zone[z], now)
                if j is not None:
                    return j
        return None

    def place(self, aid: int, f: str, j: int, now: float) -> str:
        self._alloc(f, j)
        self.running[aid] = (j, f)
        q = self.idle.get((j, f))
        if not q:
            return "cold"
        idle_since = q.popleft()
        if not q:
            del self.idle[(j, f)]
            self.idle_by_fn[f].discard(j)
        return "hot" if max(0.0, now - idle_since) <= self.dep.hot_window \
            else "warm"

    def complete(self, aid: int, now: float) -> None:
        got = self.running.pop(aid, None)
        if got is None:
            return
        j, f = got
        self._release(f, j)
        self.idle.setdefault((j, f), collections.deque()).append(now)
        self.idle_by_fn[f].add(j)


def replay(dep: Deployment, log, rng_seed: int,
           control: Optional[str] = None) -> List[Tuple[int, Optional[str],
                                                        Optional[str]]]:
    """The reference's answer to every call of the log, in order:
    ``(arrival id, worker or None, start kind or None)``."""
    if not deploy.run_limit_ok(dep, log.t_stop):
        raise ValueError("a container could outlive the keep-alive within "
                         "this run; the reference does not model expiry")
    ref = Reference(dep, random.Random(rng_seed), control)
    out = []
    for ev in log.events:
        if ev[0] == "complete":
            for aid in ev[2]:
                ref.complete(aid, ev[1])
            continue
        _kind, now, _zone, ids, _answers = ev
        for aid in ids:
            a = log.arrivals[aid]
            j = ref.decide(a.function, a.origin, now)
            if j is None:
                out.append((aid, None, None))
            else:
                out.append((aid, dep.workers[j],
                            ref.place(aid, a.function, j, now)))
    return out
