"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics
read: device busy time, time per device operation and per kernel, and the
idle gaps labelled by what the host was doing.

A device operation is an event of the ``XLA Ops`` line of a
``/device:TPU:<n>`` plane; its name is the HLO instruction text, whose
leading ``%name.N`` identifies it (a Pallas kernel's custom call carries the
jitted wrapper's name, e.g. ``bulk_decide_kernel``).  Host spans are the
``bench.*`` annotations the harness writes into the same trace; the traced
window is its ``bench.window`` span.  Device and host events share the
trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")


def op_name(event_name: str) -> str:
    """``%bulk_decide_kernel.1 = s8[...] custom-call(...)`` ->
    ``bulk_decide_kernel``."""
    m = _OP.match(event_name.strip())
    return m.group(1) if m else event_name.split(" ", 1)[0]


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``[start, end)`` intervals, in their unit."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]  # seconds on the trace clock
    ops: List[Tuple[str, float, float, int]]  # (name, start, end, device)
    spans: List[Tuple[str, float, float]]  # host bench.* spans
    devices: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clipped(self, device: Optional[int] = None):
        lo, hi = self.window
        for name, s, e, d in self.ops:
            if device is not None and d != device:
                continue
            s, e = max(s, lo), min(e, hi)
            if e > s:
                yield name, s, e

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        per = [union_seconds([(s, e) for _n, s, e in self._clipped(d)])
               for d in range(self.devices)]
        return sum(per) / max(len(per), 1)

    def op_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n, s, e in self._clipped():
            out[n] = out.get(n, 0.0) + (e - s)
        return out

    def kernel_events(self, kernel: str) -> List[float]:
        """Durations of ``kernel``'s device events in the window, in order."""
        return [e - s for n, s, e in self._clipped() if n == kernel]

    def idle_gaps(self, device: int = 0) -> List[Tuple[str, float]]:
        """Each idle stretch of ``device`` in the window, labelled by the
        host span covering its middle (``other`` when none does)."""
        lo, hi = self.window
        busy = sorted((s, e) for _n, s, e in self._clipped(device))
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        # the controller's spans follow one another without nesting
        spans = sorted((s, e, n) for n, s, e in self.spans
                       if n != "bench.window")
        starts = [s for s, _e, _n in spans]
        out = []
        for gs, ge in gaps:
            mid = (gs + ge) / 2
            k = bisect.bisect_right(starts, mid) - 1
            label = spans[k][2] if k >= 0 and mid < spans[k][1] else "other"
            out.append((label, ge - gs))
        return out


def read(path: str) -> Trace:
    """Load one ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    ops, spans = [], []
    devices = 0
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            devices = max(devices, int(m.group(1)) + 1)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((op_name(ev.name), s,
                                s + ev.duration_ns * 1e-9, int(m.group(1))))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not win:
        raise ValueError(f"{path}: no bench.window span")
    return Trace(win[0], ops, spans, devices)


def breakdown(tr: Trace) -> Dict[str, List[List]]:
    ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:10]
    by_label: Dict[str, float] = {}
    for label, s in tr.idle_gaps():
        by_label[label] = by_label.get(label, 0.0) + s
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
