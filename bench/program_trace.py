"""What the program's own names say about a profiler trace: device time
by engine phase, and idle gaps by the program's innermost span.

Phases.  The program runs each engine phase under a ``jax.named_scope``
(``engine.walk``, ``engine.advance``, ``engine.topk``; see
``repro.core.engine``), which lands in the ``op_name`` metadata of every
instruction the phase compiles to.  A trace taken without HLO protos
(as ``bench/trace.py`` takes them) names each operation on its ``XLA
Ops`` line by its instruction alone, so ``op_phases`` reads the phase of
each ``(module, instruction)`` from the compiled HLO text
(``CompletionIndex.compiled_programs``), and ``read_phases`` gives each
operation the module launched around it on the ``XLA Modules`` line.
A loop and the operations of its body are all events, nested in time,
so a phase's device time is the union of its operations' intervals,
clipped to the anchored slice and averaged over devices, never their
sum.

Host spans (``repro.spans``: the program's, nested, and the caller's)
are moved onto the trace's clock linearly through two host readings,
one right at the anchor's enter and one right at its exit
(``to_trace_clock``), and an idle gap is named by the innermost span
covering its middle (``innermost``).
"""

from __future__ import annotations

import bisect
import collections
import re

from bench.trace import (ANCHOR, DEVICE_PLANE, MODULES_LINE, NO_SPAN,
                         OPS_LINE, _clip, _short, union_ns)

PHASES = ("engine.walk", "engine.advance", "engine.topk")
_PHASE = re.compile(r"(?<![\w.])(engine\.(?:walk|advance|topk))(?!\w)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations)="
    r"\{?(%?[\w.\-]+(?:, %?[\w.\-]+)*)\}?")


def module_name(name: str) -> str:
    """A launch's program: ``jit_slab_topk(1234)`` is ``jit_slab_topk``."""
    return name.split("(", 1)[0]


def op_phases(texts) -> dict:
    """``{(module, instruction): phase}`` from compiled HLO texts.  An
    instruction without a phase in its own ``op_name`` takes the one
    phase of the computations it calls (a fusion's, a loop's body); an
    instruction two programs of one name give different phases is left
    out."""
    out: dict = {}
    for text in texts:
        lines = text.splitlines()
        module = lines[0].split()[1].rstrip(",")
        comp_phases: dict[str, set] = collections.defaultdict(set)
        instrs = []          # (name, own phase or None, called computations)
        comp = None
        for ln in lines[1:]:
            m = _COMPUTATION.match(ln)
            if m and " = " not in ln:
                comp = m.group(1)
                continue
            m = _INSTRUCTION.match(ln)
            if m is None:
                continue
            own = _OP_NAME.search(ln)
            own = _PHASE.search(own.group(1)) if own else None
            own = own.group(1) if own else None
            if own is not None and comp is not None:
                comp_phases[comp].add(own)
            called = [c.strip().lstrip("%") for g in _CALLS.findall(ln)
                      for c in g.split(",")]
            instrs.append((m.group(1), own, called))
        for name, own, called in instrs:
            if own is None:
                found = set().union(*(comp_phases[c] for c in called)) \
                    if called else set()
                own = found.pop() if len(found) == 1 else None
            if own is None:
                continue
            key = (module, name)
            out[key] = own if out.get(key, own) == own else None
    return {k: v for k, v in out.items() if v is not None}


def read_phases(pd, phases_of: dict) -> dict:
    """``{"anchor": (start_ns, end_ns) or None, "devices": [{phase:
    [(start_ns, end_ns)], "unnamed": [...]}]}`` from a
    ``jax.profiler.ProfileData``; ``phases_of`` from ``op_phases``."""
    devices, anchor = [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            launches = sorted(
                (e.start_ns, e.start_ns + e.duration_ns,
                 module_name(e.name))
                for e in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else ()))
            starts = [s for s, _, _ in launches]
            dev: dict[str, list] = {p: [] for p in PHASES + ("unnamed",)}
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                s, e_end = e.start_ns, e.start_ns + e.duration_ns
                j = bisect.bisect_right(starts, s) - 1
                module = launches[j][2] if j >= 0 and \
                    launches[j][1] >= s else None
                p = phases_of.get((module, _short(e.name).lstrip("%")))
                dev[p or "unnamed"].append((s, e_end))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor = (e.start_ns, e.start_ns + e.duration_ns)
    return {"anchor": anchor, "devices": devices}


def phase_busy(phases: dict) -> dict:
    """Seconds of each phase inside the anchor's slice: the union of its
    operations' intervals, averaged over devices; ``unnamed`` is the busy
    time no phase covers (an operation no phase claims but that runs
    inside a phase's loop is that phase's time)."""
    def span(intervals):
        return sum(e - s for s, e in union_ns(intervals))

    devs = phases["devices"]
    lo, hi = phases["anchor"]
    out = dict.fromkeys(PHASES + ("unnamed",), 0.0)
    for d in devs:
        named = []
        for p in PHASES:
            iv = _clip(d[p], lo, hi)
            out[p] += span(iv)
            named += iv
        out["unnamed"] += span(named + _clip(d["unnamed"], lo, hi)) \
            - span(named)
    return {p: v / max(len(devs), 1) * 1e-9 for p, v in out.items()}


def to_trace_clock(spans, host: tuple[float, float],
                   anchor: tuple[float, float]):
    """``(name, start, end)`` on the host clock (seconds) to the trace's
    (ns), through the host's readings at the anchor's enter and exit
    (``host``) and the anchor's two ends on the trace (``anchor``)."""
    (h0, h1), (a0, a1) = host, anchor
    scale = (a1 - a0) / (h1 - h0)
    return [(n, a0 + (s - h0) * scale, a0 + (e - h0) * scale)
            for n, s, e in spans]


def innermost(spans):
    """``doing(t)``: the innermost span ``(name, start, end)`` covering
    ``t``, or None.  Spans nest (a span opened inside another ends inside
    it), so the innermost one covering ``t`` is the latest started of
    those that have not ended before ``t``."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    starts = [s for _, s, _ in spans]

    def doing(t: float):
        for j in range(bisect.bisect_right(starts, t) - 1, -1, -1):
            if spans[j][2] >= t:
                return spans[j]
        return None

    return doing


def idle_gaps(planes: dict) -> list[tuple[float, float]]:
    """The first device's idle intervals inside the anchor's slice, from
    ``bench.trace.read_planes``."""
    lo, hi = planes["anchor"]
    merged = union_ns(_clip([(s, e) for _, s, e in
                             planes["devices"][0]["ops"]], lo, hi))
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def device_during(planes: dict, lo: float, hi: float) -> dict:
    """What the first device did in ``[lo, hi)`` (ns on the trace's
    clock): its busy ms, and the programs launched in it or running into
    it, each ``[name, ms]``, in order."""
    dev = planes["devices"][0]
    busy = sum(e - s for s, e in union_ns(
        _clip([(s, e) for _, s, e in dev["ops"]], lo, hi)))
    return {"busy_ms": busy * 1e-6,
            "programs": [[n, (e - s) * 1e-6]
                         for n, s, e in sorted(dev["modules"],
                                               key=lambda m: m[1])
                         if e > lo and s < hi]}


def name_gaps(gaps, spans, top: int = 10) -> dict:
    """Idle gaps ``[(start_ns, end_ns)]`` named by the innermost span at
    their middle: the ``top`` longest as ``[name, seconds, ms from the
    span's start to the gap's, ms from the gap's end to the span's]``
    (where in the span the device sat idle), and all idle time summed by
    name."""
    doing = innermost(spans)
    named = []
    for s, e in gaps:
        sp = doing((s + e) / 2)
        named.append([NO_SPAN, (e - s) * 1e-9, None, None] if sp is None
                     else [sp[0], (e - s) * 1e-9, (s - sp[1]) * 1e-6,
                           (sp[2] - e) * 1e-6])
    by_span: dict[str, float] = collections.defaultdict(float)
    for name, sec, _, _ in named:
        by_span[name] += sec
    return {"idle_gaps": sorted(named, key=lambda x: -x[1])[:top],
            "idle_by_span": dict(sorted(by_span.items(),
                                        key=lambda x: -x[1]))}
