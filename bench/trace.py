"""From a profiler trace to device busy time, idle share, program
launches and idle gaps by host activity.

``Tracer`` runs JAX's profiler over a slice of the window and marks the
slice with an anchor span (``ANCHOR``) on the host's line of the trace;
the host spans the drivers keep in memory (``loadgen.Spans``, on
``time.perf_counter``) are moved onto the trace's clock by the anchor's
two readings.  ``reduce_profile`` takes a ``jax.profiler.ProfileData``
and returns what the per-layer readers read.

Device planes are ``/device:TPU:<n>`` (or GPU).  On each, the ``XLA
Ops`` line holds one event per operation run and the ``XLA Modules``
line one per program launched.  Busy time is the union of the
operations' intervals inside the slice, averaged over devices; the idle
share is one minus busy over the slice.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil
import time

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "bench.traced_window"
#: what an idle gap is called when no host span of the driver covers it
NO_SPAN = "driver loop (no span)"


def union_ns(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, ascending."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _short(name: str) -> str:
    """An operation's name without its HLO text: ``%while.148 = (...)
    while(...)`` becomes ``%while.148``."""
    return name.split(" = ", 1)[0]


def read_planes(pd) -> dict:
    """The device planes' operations and launches, and the anchor span:
    ``{"devices": [{"ops": [(name, start_ns, end_ns)], "modules": [...]}],
    "anchor": (start_ns, end_ns) or None}``."""
    devices, anchor = [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(_short(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor = (e.start_ns, e.start_ns + e.duration_ns)
    return {"devices": devices, "anchor": anchor}


def reduce_profile(planes: dict, spans=(), top: int = 10) -> dict:
    """Busy and idle time of the devices inside the anchor's slice.

    ``spans``: the host's (name, start_ns, end_ns) on the trace's clock.
    Returns ``busy_s`` (union of operation intervals, averaged over
    devices), ``window_s``, ``idle_pct``, ``launches`` (programs
    launched in the slice, averaged over devices), ``device_ops`` (the
    ``top`` operations by total time, [name, seconds]) and
    ``idle_gaps`` (the ``top`` longest gaps on the first device, each
    named by the host span open at its middle, [name, seconds]), and
    ``idle_by_span`` (all idle time summed by that name)."""
    if planes["anchor"] is None:
        raise ValueError(f"the trace holds no {ANCHOR!r} span")
    lo, hi = planes["anchor"]
    devs = planes["devices"]
    if not devs or not any(d["ops"] for d in devs):
        raise ValueError("the trace holds no device operations")
    busy_ns, launches = [], []
    per_op: dict[str, float] = collections.defaultdict(float)
    for d in devs:
        merged = union_ns(_clip([(s, e) for _, s, e in d["ops"]], lo, hi))
        busy_ns.append(sum(e - s for s, e in merged))
        launches.append(sum(lo <= s < hi for _, s, _ in d["modules"]))
        for name, s, e in d["ops"]:
            if e > lo and s < hi:
                per_op[name] += min(e, hi) - max(s, lo)
    merged = union_ns(_clip([(s, e) for _, s, e in devs[0]["ops"]], lo, hi))
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]

    def doing(t: float) -> str:
        j = bisect.bisect_right(starts, t) - 1
        if j >= 0 and spans[j][2] >= t:
            return spans[j][0]
        return NO_SPAN

    named = [(doing((s + e) / 2), (e - s) * 1e-9) for s, e in gaps]
    by_span: dict[str, float] = collections.defaultdict(float)
    for name, sec in named:
        by_span[name] += sec
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "launches": sum(launches) / len(launches),
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(per_op.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, t] for n, t in
                      sorted(named, key=lambda x: -x[1])[:top]],
        "idle_by_span": dict(sorted(by_span.items(), key=lambda x: -x[1])),
    }


class Tracer:
    """Runs the profiler over ``[start, start + seconds)`` of the host
    clock, as the driver's loop ticks past those times, and keeps the
    driver's host spans in memory meanwhile."""

    def __init__(self, log_dir: str, seconds: float, counters):
        from bench.loadgen import Spans
        self.log_dir = log_dir
        self.seconds = seconds
        self.counters = counters      # () -> dict, read at both ends
        self.spans = Spans()
        self.start_at: float | None = None
        self.state = "idle"
        self.host = (0.0, 0.0)
        self.before: dict = {}
        self.after: dict = {}

    def arm(self, start_at: float) -> None:
        self.start_at = start_at

    def tick(self, now: float) -> None:
        if self.start_at is None or now < self.start_at:
            return
        if self.state == "idle":
            import jax
            shutil.rmtree(self.log_dir, ignore_errors=True)
            # device operations and host spans only: the Python tracer and
            # the programs' HLO would make the trace several times larger
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._anchor = jax.profiler.TraceAnnotation(ANCHOR)
            self._anchor.__enter__()
            self.before = self.counters()
            t0 = time.perf_counter()
            self.host = (t0, t0)
            self.spans.on = True
            self.state = "on"
        elif self.state == "on" and now >= self.host[0] + self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.state != "on":
            return
        import jax
        self.spans.on = False
        t1 = time.perf_counter()
        self.after = self.counters()
        self._anchor.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.host = (self.host[0], t1)
        self.state = "done"

    def reduce(self) -> dict:
        """Read the trace back and reduce it (see ``reduce_profile``)."""
        import jax
        files = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no trace was written under {self.log_dir}")
        planes = read_planes(jax.profiler.ProfileData.from_file(
            max(files, key=os.path.getmtime)))
        if planes["anchor"] is None:
            raise RuntimeError(f"the trace holds no {ANCHOR!r} span")
        # host clock -> trace clock, by the anchor's start
        a0 = planes["anchor"][0]
        h0 = self.host[0]
        spans = [(n, a0 + (s - h0) * 1e9, a0 + (e - h0) * 1e9)
                 for n, s, e in self.spans.items]
        out = reduce_profile(planes, spans)
        out["trace_bytes"] = sum(os.path.getsize(f) for f in files)
        return out
