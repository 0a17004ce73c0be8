"""The unified observability plane (`repro.obs`).

Covers the metrics registry (fixed-bucket histogram quantiles, collector
flattening, Prometheus rendering), the decision tracer (record chain,
Chrome-trace schema, validator negatives), the two invariants the plane
must never break — tracer-on vs tracer-off *bit-identical* decisions and
rng draws, and live block-walk verdicts agreeing with ``explain()``'s
rejection-reason vocabulary — plus the schema module (pool snapshot
bit-compat, per-zone pool residency, shard-router counters) and the
sharded route trace.
"""
import json
import random

import pytest

from repro.core.decision import REASON_MEMORY, REASON_WARMTH_TIER
from repro.obs import (
    LATENCY_BOUNDS_S,
    MetricsRegistry,
    Obs,
    StageTimers,
    Tracer,
    validate_chrome_trace,
)
from repro.obs.schema import POOL_SNAPSHOT_KEYS, pool_snapshot
from repro.platform import Platform
from repro.pool import StartCosts, WarmPool, make_policy

SCRIPT = """
d:
  workers: *
  strategy: best_first
  affinity: [!h]
i:
  - workers: *
    strategy: best_first
    affinity: [d]
  - followup: fail
h:
  workers: [w2]
"""


def _platform(**kw):
    kw.setdefault("cluster", {"w0": 8.0, "w1": 8.0, "w2": 8.0})
    plat = Platform.from_yaml(SCRIPT, **kw)
    plat.register("divide", memory=1.0, tag="d")
    plat.register("impera", memory=1.0, tag="i")
    plat.register("heavy", memory=4.0, tag="h")
    return plat


def _pool():
    return WarmPool(make_policy("fixed_ttl", ttl=100.0),
                    costs=StartCosts(cold=0.5, warm=0.1, hot=0.0),
                    budget_mb=64.0, hot_window=100.0)


# --------------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------------- #


def test_histogram_quantiles_and_snapshot():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    for x in (0.001,) * 50 + (0.1,) * 45 + (5.0,) * 5:
        h.observe(x)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(0.001 * 50 + 0.1 * 45 + 25.0)
    # interpolated quantiles land within one quarter-decade bucket of truth
    assert 0.0003 < snap["p50"] <= 0.002
    assert 0.05 < snap["p95"] <= 0.2
    assert 1.0 < snap["p99"] <= 10.0
    assert h.quantile(1.0) >= snap["p99"]


def test_histogram_empty_and_overflow():
    h = MetricsRegistry().histogram("x")
    assert h.quantile(0.5) == 0.0
    assert h.snapshot()["count"] == 0
    h.observe(1e9)  # beyond the last bound: overflow bucket
    assert h.counts[-1] == 1
    assert h.quantile(0.5) == LATENCY_BOUNDS_S[-1]


def test_registry_snapshot_flattening_and_collector_replace():
    reg = MetricsRegistry()
    reg.counter("decisions").inc(3)
    reg.gauge("workers").set(7.0)
    reg.histogram("lat_s").observe(0.01)
    reg.register_collector("pool", lambda: {"cold": 1, "by_zone": {"eu": 2}})
    snap = reg.snapshot()
    assert snap["decisions"] == 3
    assert snap["workers"] == 7.0
    assert snap["lat_s.count"] == 1
    assert snap["pool.cold"] == 1
    assert snap["pool.by_zone.eu"] == 2  # nested dicts dot-join
    # re-registering a prefix replaces, never double-reports
    reg.register_collector("pool", lambda: {"cold": 9})
    snap = reg.snapshot()
    assert snap["pool.cold"] == 9
    assert "pool.by_zone.eu" not in snap


def test_registry_prometheus_render():
    reg = MetricsRegistry()
    reg.counter("sched.decisions").inc()
    reg.histogram("stage.mask_s").observe(0.001)
    text = reg.render()
    assert "# TYPE sched_decisions counter" in text
    assert "sched_decisions 1" in text
    # conformant histogram exposition: cumulative le-buckets + sum/count
    assert "# TYPE stage_mask_s histogram" in text
    assert 'stage_mask_s_bucket{le="0.001"} 1' in text
    assert 'stage_mask_s_bucket{le="+Inf"} 1' in text
    assert "stage_mask_s_sum 0.001" in text
    assert "stage_mask_s_count 1" in text


def test_registry_prometheus_render_golden(tmp_path):
    # a tiny registry with custom bounds, rendered against the checked-in
    # golden file — any exposition-format drift must be deliberate
    from pathlib import Path

    reg = MetricsRegistry()
    reg.counter("requests").inc(5)
    reg.gauge("inflight").set(2.0)
    h = reg.histogram("latency_s", bounds=(0.001, 0.01, 0.1, 1.0))
    for x in (0.0005, 0.005, 0.005, 0.05, 2.0):
        h.observe(x)
    reg.register_collector("pool", lambda: {"cold": 3, "rate": 0.5})
    # nested collector (the resilience bundle's snapshot shape): nested
    # dicts dot-join, dots become underscores in the exposition
    reg.register_collector("resilience", lambda: {
        "shed": 2, "queue_depth": 1,
        "tenants": {"gold": {"admitted": 4, "rate": 2}}})
    golden = Path(__file__).parent / "golden" / "metrics.prom"
    assert reg.render() == golden.read_text()


def test_stage_timers_sampling():
    reg = MetricsRegistry()
    tm = StageTimers(reg, sample=4)
    fired = [tm.sample() for _ in range(12)]
    assert fired == [False, False, False, True] * 3  # deterministic 1-in-4
    tm.observe("mask_build", 0.002)
    assert reg.histogram("sched.stage.mask_build_s").count == 1
    with pytest.raises(ValueError):
        StageTimers(reg, sample=3)  # not a power of two


@pytest.mark.parametrize("annotated", [False, True])
def test_stage_timers_span_and_count(annotated):
    reg = MetricsRegistry()
    opened = []

    class Ann:
        def __init__(self, name, **meta):
            opened.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    tm = StageTimers(reg, sample=1, annotate=Ann if annotated else None)
    with tm.span("item_decide", decision=7):
        with tm.span("mask_build"):
            pass
        with tm.span("valid_pad"):
            pass
    with pytest.raises(KeyError):  # a span closes when its block raises
        with tm.span("commit"):
            raise KeyError("f")
    tm.count("valid_launches")
    tm.count("valid_h2d_bytes", 2048)
    tm.count("valid_h2d_bytes", 1024)
    snap = reg.snapshot()
    for stage in ("item_decide", "mask_build", "valid_pad", "commit"):
        assert snap[f"sched.stage.{stage}_s.count"] == 1
    assert snap["sched.stage.valid_launches"] == 1
    assert snap["sched.stage.valid_h2d_bytes"] == 3072
    # the children's time is the parent's inner time; leaves have none
    h = reg.histogram
    inner = snap["sched.stage.item_decide_inner_s"]
    assert inner == pytest.approx(h("sched.stage.mask_build_s").sum
                                  + h("sched.stage.valid_pad_s").sum)
    assert 0 < inner <= h("sched.stage.item_decide_s").sum
    assert snap["sched.stage.mask_build_inner_s"] == 0.0
    # one trace span per span, named as the histogram without ``_s``, and
    # only when the caller hands in an ``annotate``
    assert opened == ([("sched.stage.item_decide", {"decision": 7}),
                       ("sched.stage.mask_build", {}),
                       ("sched.stage.valid_pad", {}),
                       ("sched.stage.commit", {})] if annotated else [])


def test_obs_imports_without_jax():
    import subprocess
    import sys

    code = ("import sys; sys.modules['jax'] = None; import repro.obs; "
            "repro.obs.StageTimers(repro.obs.MetricsRegistry(), sample=1)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# --------------------------------------------------------------------------- #
# tracer: records, exports, validator
# --------------------------------------------------------------------------- #


def test_tracer_record_chain_and_jsonl():
    tr = Tracer()
    d1 = tr.begin(1.0, "f", "eu")
    tr.blocks("f", 0, "w0")
    tr.decision(1.0, "f", "w0", "eu")
    tr.invoke("act-1", 1.0, "f", "w0", "warm", 0.1, "eu")
    tr.complete("act-1", 2.5)
    recs = tr.records()
    assert [r["kind"] for r in recs] == [
        "begin", "blocks", "decision", "invoke", "complete"]
    assert recs[0]["id"] == f"d{d1}"
    assert recs[3]["decision_id"] == f"d{d1}"
    assert recs[1]["t"] == 1.0  # blocks stamped with the begin-scope time
    lines = tr.to_jsonl().strip().splitlines()
    assert len(lines) == 5
    assert json.loads(lines[3])["start_kind"] == "warm"


def test_tracer_ring_bound():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.complete(f"act-{i}", float(i))
    assert len(tr.events) == 4
    assert tr.records()[0]["id"] == "act-6"  # oldest dropped first
    assert tr.dropped_spans == 6  # every eviction is counted, not silent


def test_tracer_dropped_spans_in_snapshot():
    obs = Obs(tracer=Tracer(capacity=4))
    for i in range(7):
        obs.tracer.complete(f"act-{i}", float(i))
    snap = obs.snapshot()
    assert snap["tracer.records"] == 4
    assert snap["tracer.dropped_spans"] == 3
    assert "tracer_dropped_spans 3" in obs.render()


def test_chrome_trace_layout():
    tr = Tracer()
    tr.begin(1.0, "f", "eu")
    tr.invoke("act-1", 1.0, "f", "eu0", "cold", 0.5, "eu")
    tr.complete("act-1", 3.0)
    tr.begin(4.0, "g")
    tr.invoke("act-2", 4.0, "g", "w9", "none", 0.0, None)
    ct = tr.chrome_trace()
    assert validate_chrome_trace(ct) == []
    evs = ct["traceEvents"]
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"zone:eu", "zone:cluster"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["dur"] == pytest.approx(2e6)
    assert xs[0]["args"]["decision_id"] == "d1"
    # unmatched invoke renders as an instant, not a zero-length span
    assert any(e["ph"] == "i" and e["cat"] == "invoke" for e in evs)


def test_chrome_trace_validator_negatives():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "?", "name": "x"}]})
    bad_sort = {"traceEvents": [
        {"ph": "i", "name": "a", "ts": 5, "s": "t", "pid": 1, "tid": 0},
        {"ph": "i", "name": "b", "ts": 1, "s": "t", "pid": 1, "tid": 0}]}
    assert any("unsorted" in e for e in validate_chrome_trace(bad_sort))
    bad_dur = {"traceEvents": [
        {"ph": "X", "name": "a", "ts": 1, "dur": -2, "pid": 1, "tid": 0}]}
    assert any("dur" in e for e in validate_chrome_trace(bad_dur))
    unmatched = {"traceEvents": [
        {"ph": "B", "name": "a", "ts": 1, "pid": 1, "tid": 0}]}
    assert any("unclosed" in e for e in validate_chrome_trace(unmatched))
    ok = {"traceEvents": [
        {"ph": "B", "name": "a", "ts": 1, "pid": 1, "tid": 0},
        {"ph": "E", "name": "a", "ts": 2, "pid": 1, "tid": 0}]}
    assert validate_chrome_trace(ok) == []


# --------------------------------------------------------------------------- #
# invariant: tracing changes nothing
# --------------------------------------------------------------------------- #


def _drive(plat, n=40):
    rng = random.Random(7)
    mix = random.Random(11)
    out = []
    for _ in range(n):
        f = mix.choice(["divide", "impera", "heavy"])
        d = plat.invoke(f, rng)
        out.append((f, d.worker, d.start_kind))
        if d.worker is not None:
            plat.complete(d)
    # the rng's post-run stream is part of the fingerprint: a traced run
    # must consume exactly the same draws as an untraced one
    return out, [rng.random() for _ in range(3)]


def test_tracing_is_bit_identical():
    plain = _drive(_platform(pool=_pool()))
    traced_obs = Obs.enabled(verdicts=True)
    traced = _drive(_platform(pool=_pool(), obs=traced_obs))
    assert plain == traced
    assert len(traced_obs.tracer.events) > 0


def test_attach_detach_round_trip():
    obs = Obs.enabled()
    plat = _platform()
    plat.attach_obs(obs)
    plat.invoke("divide", random.Random(0))
    n = len(obs.tracer.events)
    assert n > 0
    plat.attach_obs(None)
    plat.invoke("divide", random.Random(0))
    assert len(obs.tracer.events) == n  # detached: nothing recorded
    plat.attach_obs(obs)
    plat.invoke("divide", random.Random(0))
    assert len(obs.tracer.events) > n


# --------------------------------------------------------------------------- #
# invariant: live block-walk verdicts agree with explain()
# --------------------------------------------------------------------------- #


def _assert_blocks_agree(blocks_rec, explained):
    walked = dict(blocks_rec["verdicts"])
    assert explained.trace is not None
    assert len(walked) == len(explained.trace)
    for bt in explained.trace:
        live = walked[bt.index]
        assert live == tuple(
            (v.worker, v.ok, v.reason) for v in bt.workers), (
            f"block {bt.index}: live trace disagrees with explain()")


def test_live_verdicts_agree_with_explain():
    obs = Obs.enabled(verdicts=True)
    plat = _platform(pool=_pool(), obs=obs)
    rng = random.Random(7)
    mix = random.Random(11)
    for _ in range(30):
        f = mix.choice(["divide", "impera", "heavy"])
        explained = plat.explain(f)
        d = plat.invoke(f, rng)
        rec = plat.obs.tracer.records()[-2 if d.worker else -1]
        if rec["kind"] != "blocks":  # unschedulable with no pool acquire
            rec = next(r for r in reversed(plat.obs.tracer.records())
                       if r["kind"] == "blocks")
        assert rec["function"] == f
        _assert_blocks_agree(rec, explained)
        assert rec["worker"] == explained.worker
        if d.worker is not None:
            plat.complete(d)


def test_live_verdicts_memory_and_warmth_reasons():
    obs = Obs.enabled(verdicts=True)
    plat = _platform(pool=_pool(), obs=obs)
    rng = random.Random(3)
    # fill w0..w2 until `heavy` (4.0) stops fitting somewhere: memory
    # rejections must surface in the live walk with explain()'s vocabulary
    live = []
    for _ in range(4):
        d = plat.invoke("heavy", rng)
        if d.worker:
            live.append(d)
    reasons = {v[2] for r in plat.obs.tracer.records()
               if r["kind"] == "blocks" and r["verdicts"]
               for _b, vs in r["verdicts"] for v in vs}
    assert REASON_MEMORY in reasons
    # warm the pool on one worker, then a warmth-tier drop appears once
    # another worker is also valid but colder
    for d in live:
        plat.complete(d)
    d1 = plat.invoke("divide", rng)
    plat.complete(d1)  # released: an idle `divide` container now warms d1.worker
    n = len(plat.obs.tracer.records())
    d2 = plat.invoke("divide", rng)
    assert d2.worker == d1.worker and d2.start_kind != "cold"
    reasons = {v[2] for r in plat.obs.tracer.records()[n:]
               if r["kind"] == "blocks" and r["verdicts"]
               for _b, vs in r["verdicts"] for v in vs}
    assert REASON_WARMTH_TIER in reasons


# --------------------------------------------------------------------------- #
# schema module + stats surfaces
# --------------------------------------------------------------------------- #


def test_pool_snapshot_schema_bit_compat():
    pool = _pool()
    plat = _platform(pool=pool)
    rng = random.Random(1)
    for _ in range(6):
        d = plat.invoke("divide", rng)
        if d.worker is not None:
            plat.complete(d)
    snap = pool.metrics.snapshot()
    assert tuple(snap.keys()) == POOL_SNAPSHOT_KEYS
    assert snap == pool_snapshot(pool.metrics)
    assert snap["total_starts"] >= 1


def test_pool_metrics_register_into():
    pool = _pool()
    reg = MetricsRegistry()
    pool.metrics.register_into(reg)
    assert reg.snapshot()["pool.cold_starts"] == 0


def test_platform_stats_zone_residency_and_router_counters():
    pool = WarmPool(make_policy("fixed_ttl", ttl=100.0),
                    costs=StartCosts(cold=0.5, warm=0.1, hot=0.0),
                    budget_mb=256.0, hot_window=100.0)
    plat = Platform(
        "t:\n  workers: *\n  topology: local_first\n",
        cluster={"eu0": 8.0, "eu1": 8.0, "us0": 8.0},
        zones={"eu0": "eu", "eu1": "eu", "us0": "us"},
        functions={"f": (1.0, "t")}, pool=pool)
    d = plat.invoke("f", zone="us")
    assert d.worker == "us0"
    plat.complete(d)
    stats = plat.stats()
    assert stats["zones"]["us"]["pool_idle"] == 1  # released container idles
    assert stats["zones"]["eu"]["pool_idle"] == 0
    assert stats["zone_masked"] == 0
    assert "pool" in stats and stats["pool"]["total_starts"] == 1
    plat.close()


def test_shard_router_route_trace_and_exhaustion_counter():
    obs = Obs.enabled()
    plat = Platform(
        "t:\n  workers: *\n  topology: local_first\n",
        cluster={"eu0": 1.0, "us0": 8.0},
        zones={"eu0": "eu", "us0": "us"},
        functions={"f": (4.0, "t")}, obs=obs)
    d = plat.invoke("f", zone="eu")  # does not fit in eu: spills to us
    assert d.worker == "us0"
    routes = [r for r in obs.tracer.records() if r["kind"] == "route"]
    assert len(routes) == 1
    r = routes[0]
    assert r["zone"] == "us" and r["hops"] >= 1
    assert any(z == "eu" for _b, z in r["tried"])  # eu tried first, exhausted
    assert plat.stats()["zone_exhausted"] >= 1
    plat.close()


def test_forecast_planner_action_counters():
    from repro.forecast import ArrivalForecast, ForecastPlanner, PlanConfig

    fc = ArrivalForecast(tau=5.0)
    pool = _pool()
    plat = _platform(pool=pool)
    planner = ForecastPlanner(fc, plat.compiled, plat.registry, PlanConfig())
    assert planner.stats["epochs"] == 0
    for t in range(20):
        fc.observe("divide", float(t))
    planner.plan(plat.state.conf(), pool, 20.0)
    assert planner.stats["epochs"] == 1
    assert set(planner.stats) == {"epochs", "prewarms", "migrations",
                                  "retires"}
