"""The program's spans and their reduction (``bench/spans.py``).

A tiny rehearsal of the zoned cell, in the Pallas interpreter, with the
stage timers on and writing spans: one validity launch per zone hop and per
delegated decision, one commit per placed decision, and the same decisions
and rng draws as the same calls made untimed.  A synthetic trace pins the
innermost-span labelling and self time.  A 0.4 s window of divimp2z-steady
recorded on one TPU v5e with ``bench/spans.py --out`` (its registry snapshot
and session counters in the ``.json`` beside it) pins the reduction against
a real trace.
"""
from __future__ import annotations

import collections
import contextlib
import json
import random
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import check, harness, metrics, spans, stages, trace

import bench_tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = harness.load_benchmark(ROOT)
FIX = ROOT / "bench" / "fixtures" / "divimp2z-steady-spans.xplane.pb"
NEW = ["valid_pad_ms", "valid_launch_ms", "valid_fetch_ms", "valid_h2d_kb",
       "item_self_ms", "route_self_ms", "commit_ms"]
SEED = 2**33 + 5


# ---- the rehearsal --------------------------------------------------------- #

def _stand():
    cell = harness.find_cell(SPEC, "divimp2z-steady")
    return harness.stand_up(cell, SEED, 0.5, backend="pallas",
                            interpret=True)


def _replay(stand, log, rng):
    """The window's completions and calls, in order and at their instants,
    on ``stand`` with no timers: the answers of every call."""
    got, decided = [], {}
    for ev in log.events:
        stand.clock.now = ev[1]
        if ev[0] == "complete":
            for aid in ev[2]:
                stand.plat.complete(decided.pop(aid))
            continue
        _call, _t, zone, ids, _answers = ev
        ds = stand.plat.decide_batch([log.arrivals[a].function for a in ids],
                                     rng, apply=True, zone=zone)
        decided.update(zip(ids, ds))
        got.append([(d.worker, d.start_kind) for d in ds])
    return got


@pytest.fixture(scope="module")
def rehearsal():
    with pytest.MonkeyPatch.context() as mp:
        bench_tiny.install(mp)
        names = []

        @contextlib.contextmanager
        def annotate(name, **meta):
            names.append((name, meta))
            yield

        stand = _stand()
        stats0 = dict(stand.plat.session.stats)
        rng = random.Random(SEED)
        log, obs = spans.window(stand, annotate, rng)
        stats = {k: v - stats0.get(k, 0)
                 for k, v in stand.plat.session.stats.items()
                 if isinstance(v, int)}
        ok, _counts = check.check(stand.dep, log, SEED)
        untimed = _stand()
        rng_untimed = random.Random(SEED)
        answers = _replay(untimed, log, rng_untimed)
        yield SimpleNamespace(names=names, stats=stats, log=log, obs=obs,
                              ok=ok, rng=rng, answers=answers,
                              rng_untimed=rng_untimed)


def test_rehearsal_launches_once_per_hop_and_delegated(rehearsal):
    snap = rehearsal.obs.registry.snapshot()
    st = rehearsal.stats
    assert rehearsal.ok
    assert st["zone_hops"] > 0 and st["delegated"] > 0
    assert snap["sched.stage.valid_launches"] == st["zone_hops"] + st[
        "delegated"]
    n = collections.Counter(name for name, _m in rehearsal.names)
    for stage in ("valid_pad", "valid_launch", "valid_fetch",
                  "item_decide"):
        assert n[f"sched.stage.{stage}"] == snap[
            "sched.stage.valid_launches"]
    assert n["sched.stage.shard_route"] == st["routed"]
    # routed and delegated decisions are labelled with a decision count
    assert all("decision" in m for name, m in rehearsal.names
               if name in ("sched.stage.shard_route",
                           "sched.stage.item_decide"))


def test_rehearsal_commits_once_per_placed_decision(rehearsal):
    placed = sum(1 for ev in rehearsal.log.events if ev[0] == "call"
                 for w, _k in ev[4] if w is not None)
    n = collections.Counter(name for name, _m in rehearsal.names)
    assert placed > 0
    assert n["sched.stage.commit"] == placed
    assert n["sched.stage.pool_acquire"] == placed
    run = SimpleNamespace(obs=rehearsal.obs)
    for m in NEW:
        assert metrics.reader(m)(run) is not None, m


def test_rehearsal_timers_change_no_decision_or_draw(rehearsal):
    timed = [ev[4] for ev in rehearsal.log.events if ev[0] == "call"]
    assert timed and rehearsal.answers == timed
    assert rehearsal.rng_untimed.getstate() == rehearsal.rng.getstate()


# ---- the reduction on a synthetic trace ----------------------------------- #

def test_innermost_labelling_and_self_time():
    ops = [("affinity_valid_kernel", 1.0, 2.0, 0), ("copy", 6.0, 7.0, 0)]
    sp = [("bench.window", 0.0, 10.0, 0),
          ("bench.decide_batch", 0.5, 5.0, 0),
          ("sched.stage.shard_route", 0.5, 4.5, 0),
          ("sched.stage.item_decide", 0.8, 3.0, 0),
          ("sched.stage.valid_launch", 0.9, 1.5, 0),
          ("bench.wait", 5.0, 9.0, 0)]
    tr = trace.Trace((0.0, 10.0), ops, [s[:3] for s in sp
                                        if s[0].startswith("bench.")], 1)
    pieces = spans.idle_pieces(tr, sp)
    by = collections.defaultdict(float)
    for label, s in pieces:
        by[label] += s
    want = {"other": 1.5, "sched.stage.shard_route": 1.8,
            "sched.stage.item_decide": 1.1, "sched.stage.valid_launch": 0.1,
            "bench.decide_batch": 0.5, "bench.wait": 3.0}
    assert set(by) == set(want)
    for k, v in want.items():
        assert by[k] == pytest.approx(v), k
    assert sum(s for _l, s in pieces) == pytest.approx(
        tr.window_s - tr.busy_s())
    assert spans.self_seconds(sp, "sched.stage.shard_route") == \
        pytest.approx(4.0 - 2.2)
    assert spans.self_seconds(sp, "sched.stage.item_decide") == \
        pytest.approx(2.2 - 0.6)
    assert spans.self_seconds(sp, "bench.decide_batch") == \
        pytest.approx(0.5)
    assert spans.nesting_faults(sp) == 0
    assert spans.nesting_faults(sp + [("a", 1.0, 3.0, 1),
                                      ("b", 2.0, 4.0, 1)]) == 1


# ---- the reduction on a trace recorded on the chip ------------------------ #

@pytest.fixture(scope="module")
def recorded():
    tr = trace.read(FIX)
    sp = [s for s in spans.read(FIX)
          if tr.window[0] <= s[1] and s[2] <= tr.window[1]]
    saved = json.loads(Path(str(FIX) + ".json").read_text())
    snap = saved["snapshot"]
    run = SimpleNamespace(obs=SimpleNamespace(
        registry=SimpleNamespace(snapshot=lambda: snap)))
    return SimpleNamespace(tr=tr, spans=sp, stats=saved["stats"], snap=snap,
                           run=run)


def _named(rec, stage):
    return [s for s in rec.spans if s[0] == stages.PREFIX + "." + stage]


@pytest.mark.parametrize("name", NEW + ["route_ms"])
def test_recorded_readers_return_a_number(recorded, name):
    v = metrics.reader(name)(recorded.run)
    assert isinstance(v, float) and v > 0


def test_recorded_launch_spans_count_the_kernel_events(recorded):
    events = recorded.tr.kernel_events("affinity_valid_kernel")
    st = recorded.stats
    assert len(events) == st["zone_hops"] + st["delegated"] > 0
    assert len(_named(recorded, "valid_launch")) == len(events)
    assert recorded.snap["sched.stage.valid_launches"] == len(events)


def test_recorded_kernels_run_inside_item_decide(recorded):
    """The k-th kernel event lies inside the k-th ``item_decide`` span
    once the device's events are moved by one constant, the least that
    starts no kernel before the host began to ship its inputs: the
    profiler puts them up to 0.32 ms early in this trace (and its unpad
    programs 0.80-1.82 ms early against the fetch spans that dispatch
    them)."""
    items = sorted((s, e) for _n, s, e, _t in _named(recorded,
                                                     "item_decide"))
    kernels = sorted((s, e) for n, s, e, _d in recorded.tr.ops
                     if n == "affinity_valid_kernel")
    lead = spans.device_lead(recorded.tr, recorded.spans)
    assert len(kernels) == len(items) > 0
    assert 0 < lead < 0.5e-3
    for (a, b), (s, e) in zip(items, kernels):
        assert a <= s + lead and e + lead <= b


def test_recorded_spans_nest_and_self_time_adds_up(recorded):
    sp = recorded.spans
    assert spans.nesting_faults(sp) == 0
    for stage in ("shard_route", "item_decide", "commit"):
        parents = _named(recorded, stage)
        total = sum(e - s for _n, s, e, _t in parents)
        # the children by containment, found without the reduction's sweep
        children = 0.0
        for _n, ps, pe, pt in parents:
            inner = [c for c in sp if c[3] == pt and ps <= c[1]
                     and c[2] <= pe and c[2] - c[1] < pe - ps]
            children += sum(c[2] - c[1] for c in inner
                            if not any(o is not c and o[1] <= c[1]
                                       and c[2] <= o[2]
                                       and o[2] - o[1] > c[2] - c[1]
                                       for o in inner))
        own = spans.self_seconds(sp, stages.PREFIX + "." + stage)
        assert 0 < own < total
        assert own + children == pytest.approx(total, rel=1e-9)


def test_recorded_idle_pieces_sum_to_the_idle_time(recorded):
    tr = recorded.tr
    pieces = spans.idle_pieces(tr, recorded.spans)
    assert sum(s for _l, s in pieces) == pytest.approx(
        tr.window_s - tr.busy_s(), abs=1e-9)
    assert any(label.startswith("sched.stage.") for label, _s in pieces)


def test_recorded_histograms_and_spans_agree(recorded):
    """One clock, two readings: the router's histogram and its trace
    spans; the padded bytes and the shapes' reckoning."""
    route = _named(recorded, "shard_route")
    mean = sum(e - s for _n, s, e, _t in route) / len(route) * 1e3
    assert metrics.reader("route_ms")(recorded.run) == pytest.approx(
        mean, rel=0.05)
    st = recorded.stats
    hops, delegated = st["zone_hops"], st["delegated"]
    # a zone hop pads to F 128, W 3072, T 128; a delegated decision to
    # W 6144 (the whole cluster)
    hop_b, whole_b = 2020864, 4023808
    want = (hops * hop_b + delegated * whole_b) / (hops + delegated) / 1e3
    assert metrics.reader("valid_h2d_kb")(recorded.run) == pytest.approx(
        want, rel=1e-9)
