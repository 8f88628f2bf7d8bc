"""What the program's names say about a trace, on small synthetic inputs:
engine phases read from compiled HLO text, device time by phase as the
union of each phase's operations per launched module, host spans moved
onto the trace's clock through both ends of the anchor, idle gaps named
by the innermost span, and the explain report's span times."""

import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import explain, program_trace as pt, trace  # noqa: E402

MS = 1_000_000_000       # picoseconds per millisecond

BATCH_HLO = """HloModule jit_complete_batch, is_scheduled=true

%fused_computation.1 (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %add.1 = s32[4]{0} add(%p, %p), metadata={op_name="jit(complete_batch)/engine.walk/while/body/add"}
}

%body.2 (t: (s32[4])) -> (s32[4]) {
  %t = (s32[4]{0}) parameter(0)
  %x = s32[4]{0} get-tuple-element(%t), index=0
  %fusion.1 = s32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple.2 = (s32[4]{0}) tuple(%fusion.1)
}

ENTRY %main.3 (a: s32[4]) -> s32[4] {
  %a = s32[4]{0} parameter(0)
  %while.5 = (s32[4]{0}) while(%t0), condition=%cond.4, body=%body.2, metadata={op_name="jit(complete_batch)/engine.walk/while"}
  %fusion.2 = s32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(complete_batch)/engine.walk/while/body/x"}
  %while.9 = (s32[4]{0}) while(%t1), condition=%cond.6, body=%body.7, metadata={op_name="jit(complete_batch)/engine.topk/while"}
  %sort.3 = s32[4]{0} sort(%a), dimensions={0}, metadata={op_name="jit(complete_batch)/engine.topk/sort"}
  ROOT %copy.7 = s32[4]{0} copy(%a)
}
"""
TOPK_HLO = """HloModule jit_slab_topk, is_scheduled=true

ENTRY %main.1 (a: s32[4]) -> s32[4] {
  ROOT %while.5 = (s32[4]{0}) while(%a), condition=%c, body=%b, metadata={op_name="jit(slab_topk)/engine.topk/while"}
}
"""


def _events(evs):
    return "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
        f"duration_ps: {int(d * MS)} }}\n" for m, s, d in evs)


def _meta(names):
    return "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for i, n in enumerate(names, 1))


# A 10 ms slice from 0.  complete_batch runs [1, 9) ms: the walk's loop
# [1, 5) with body ops [1.5, 2.5) and [3, 4.5), phase 2's loop [5, 8)
# with a sort [5.5, 6), then an unnamed copy [8, 8.5).  slab_topk runs
# [9.2, 9.8) with its loop, named like complete_batch's walk loop,
# [9.3, 9.7) and a last op past the slice [10, 11).
OPS = ["%while.5 = (s32[4]) while(%t0)", "%fusion.1 = s32[4] fusion(%x)",
       "%fusion.2 = s32[4] fusion(%a)", "%while.9 = (s32[4]) while(%t1)",
       "%sort.3 = s32[4] sort(%a)", "%copy.7 = s32[4] copy(%a)"]
PROFILE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_events([(1, 1, 8), (2, 9.2, 0.6), (2, 10, 1)])}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events([(3, 1, 4), (4, 1.5, 1), (5, 3, 1.5), (6, 5, 3), (7, 5.5, 0.5),
              (8, 8, 0.5), (3, 9.3, 0.4), (3, 10, 1)])}
  }}
  {_meta(["jit_complete_batch(11)", "jit_slab_topk(12)"] + OPS)}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events([(1, 0, 10)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{trace.ANCHOR}" }} }}
}}
"""


def test_op_phases_from_metadata_and_called_computations():
    got = pt.op_phases([BATCH_HLO, TOPK_HLO])
    assert got == {
        ("jit_complete_batch", "add.1"): "engine.walk",
        ("jit_complete_batch", "fusion.1"): "engine.walk",
        ("jit_complete_batch", "while.5"): "engine.walk",
        ("jit_complete_batch", "fusion.2"): "engine.walk",
        ("jit_complete_batch", "while.9"): "engine.topk",
        ("jit_complete_batch", "sort.3"): "engine.topk",
        ("jit_slab_topk", "while.5"): "engine.topk"}
    # two programs of one name that disagree leave the instruction out
    other = BATCH_HLO.replace("engine.topk/sort", "engine.advance/sort")
    assert ("jit_complete_batch", "sort.3") not in pt.op_phases(
        [BATCH_HLO, other])


def test_phase_time_is_the_union_of_its_ops_per_module():
    pd = jax.profiler.ProfileData.from_text_proto(PROFILE)
    phases = pt.read_phases(pd, pt.op_phases([BATCH_HLO, TOPK_HLO]))
    assert phases["anchor"] == (0, 10e6)
    busy = pt.phase_busy(phases)
    assert busy == {"engine.walk": pytest.approx(0.004),
                    "engine.advance": 0.0,
                    "engine.topk": pytest.approx(0.0034),
                    "unnamed": pytest.approx(0.0005)}
    # the phases and the unnamed rest make up the device's busy time
    red = trace.reduce_profile(trace.read_planes(pd))
    assert sum(busy.values()) == pytest.approx(red["busy_s"])


def test_host_spans_move_through_both_ends_of_the_anchor():
    # 10 ms of host clock span 10.01 ms of the trace's
    spans = [("submit", 100.0, 100.005), ("poll", 100.01, 100.01)]
    got = pt.to_trace_clock(spans, (100.0, 100.01), (5e6, 15.01e6))
    assert got[0][1] == pytest.approx(5e6)
    assert got[0][2] == pytest.approx(5e6 + 5.005e6)
    assert got[1][1] == pytest.approx(15.01e6)


def test_gaps_take_the_innermost_span_covering_them():
    spans = [("complete", 0, 10), ("index.complete", 1, 9),
             ("index.pad", 1, 1.5), ("index.fetch", 2, 5)]
    gaps = [(2.5, 3.5), (5.6, 6.4), (9.3, 9.7), (10.9, 11.1)]
    named = pt.name_gaps(gaps, spans)
    assert [g[0] for g in named["idle_gaps"]] == [
        "index.fetch", "index.complete", "complete", trace.NO_SPAN]
    assert named["idle_by_span"]["index.fetch"] == pytest.approx(1e-9)
    # where in its span each gap fell: ms from the span's start to the
    # gap's, and from the gap's end to the span's (the spans here are in
    # ns, as on the trace's clock)
    assert named["idle_gaps"][0][2:] == [pytest.approx(0.5e-6),
                                         pytest.approx(1.5e-6)]
    assert named["idle_gaps"][3][2:] == [None, None]
    # the gaps of a profile, named
    planes = trace.read_planes(jax.profiler.ProfileData.from_text_proto(
        PROFILE))
    assert pt.idle_gaps(planes) == [(0, 1e6), (8.5e6, 9.3e6),
                                    (9.7e6, 10e6)]
    # and what the device did during a host interval
    assert pt.device_during(planes, 0, 2e6) == {
        "busy_ms": pytest.approx(1.0),
        "programs": [["jit_complete_batch(11)", pytest.approx(8.0)]]}


def test_span_self_time_and_slow_calls():
    items = [("poll", 0.0, 0.2), ("sched.flush", 0.01, 0.05),
             ("sched.dispatch", 0.01, 0.02), ("sched.settle", 0.03, 0.05),
             ("sched.device_get", 0.03, 0.045), ("submit", 0.3, 0.301)]
    rows = explain.by_span(items, 0.0, 1.0)
    assert rows["poll"] == [1, pytest.approx(0.2), pytest.approx(0.16)]
    assert rows["sched.flush"][2] == pytest.approx(0.01)
    assert rows["sched.settle"][2] == pytest.approx(0.005)
    slow = explain.stalls(items, 0.0, 1.0)
    assert [s["call"] for s in slow] == ["poll"]
    assert slow[0]["inside_ms"] == {
        "sched.device_get": pytest.approx(15), "sched.dispatch":
        pytest.approx(10), "sched.flush": pytest.approx(40),
        "sched.settle": pytest.approx(20)}
