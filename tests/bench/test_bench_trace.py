"""The trace reduction on two small traces recorded on one TPU v5e with
``--trace 1`` windows of the two cells: 0.40 s of divimp2z-steady (40
calls, 54 zone hops and 4 delegated decisions, so 58 per-item kernel
launches) and 0.31 s of azure16k-steady (20 calls: 19 bulk waves and one
single arrival on the per-item path)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

FIX = Path(__file__).resolve().parents[2] / "bench" / "fixtures"


@pytest.fixture(scope="module")
def traces():
    return {n: trace.read(FIX / f"{n}.xplane.pb")
            for n in ("divimp2z-steady", "azure16k-steady")}


def _busy_by_sweep(intervals):
    """Busy time by a sweep over start/end events: an independent way to
    the union's length."""
    events = sorted([(s, 1) for s, _e in intervals]
                    + [(e, -1) for _s, e in intervals],
                    key=lambda x: (x[0], -x[1]))
    depth, last, busy = 0, None, 0.0
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("name,window,calls", [
    ("divimp2z-steady", 0.403195973, 40), ("azure16k-steady", 0.315381923,
                                           20)])
def test_window_spans_and_busy(traces, name, window, calls):
    tr = traces[name]
    assert tr.devices == 1
    assert tr.window_s == pytest.approx(window, abs=1e-9)
    assert sum(1 for n, _s, _e in tr.spans
               if n == "bench.decide_batch") == calls
    lo, hi = tr.window
    clipped = [(max(s, lo), min(e, hi)) for _n, s, e, _d in tr.ops
               if min(e, hi) > max(s, lo)]
    busy = tr.busy_s()
    assert busy == pytest.approx(_busy_by_sweep(clipped), abs=1e-12)
    assert 0 < busy < 0.01 * window * 2
    gaps = tr.idle_gaps()
    assert busy + sum(s for _l, s in gaps) == pytest.approx(window,
                                                           abs=1e-9)
    labels = {lab for lab, _s in gaps}
    assert labels <= {"bench.wait", "bench.decide_batch", "bench.complete",
                      "other"}
    assert "bench.decide_batch" in labels


@pytest.mark.parametrize("name,kernel,launches", [
    ("divimp2z-steady", "affinity_valid_kernel", 58),
    ("divimp2z-steady", "bulk_decide_kernel", 0),
    ("azure16k-steady", "bulk_decide_kernel", 19),
    ("azure16k-steady", "affinity_valid_kernel", 1)])
def test_kernel_launches_and_time(traces, name, kernel, launches):
    tr = traces[name]
    times = tr.kernel_events(kernel)
    assert len(times) == launches
    assert all(t > 0 for t in times)
    if launches:
        assert sum(times) == pytest.approx(tr.op_seconds()[kernel])
        # every launch runs inside the harness's span around its call
        spans = [(s, e) for n, s, e in tr.spans if n == "bench.decide_batch"]
        for n, s, e, _d in tr.ops:
            if n == kernel:
                assert any(a <= s and e <= b for a, b in spans)


def test_breakdown_names_the_kernel_first(traces):
    b = trace.breakdown(traces["azure16k-steady"])
    assert b["device_ops"][0][0] == "bulk_decide_kernel"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert [n for n, _s in b["idle_gaps"]][0] in ("bench.decide_batch",
                                                  "bench.wait")


@pytest.mark.parametrize("text,name", [
    ("%bulk_decide_kernel.1 = (s8[128,16384]{1,0}) custom-call(...)",
     "bulk_decide_kernel"),
    ("%copy-start = (f32[384,1]{0,1:T(1,128)S(1)}) copy-start(...)",
     "copy-start"),
    ("%copy.2 = s32[128,1]{1,0} copy(s32[128,1]{0,1} %max_conc.1)", "copy"),
    ("%constant_dynamic-slice_fusion = s8[3,16384]{1,0} fusion(...)",
     "constant_dynamic-slice_fusion")])
def test_op_names(text, name):
    assert trace.op_name(text) == name


def test_union():
    assert trace.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert trace.union_seconds([]) == 0
