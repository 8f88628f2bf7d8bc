"""The trace reduction on a small synthetic profile: busy time is the
union of the device's operations inside the anchored slice, launches are
the programs started in it, and idle gaps are named by the host span open
during them."""

import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

MS = 1_000_000_000       # picoseconds per millisecond


def _events(evs):
    return "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
        f"duration_ps: {int(d * MS)} }}\n" for m, s, d in evs)


# A 10 ms slice from t=1 ms.  Ops (ms): [0.5, 2) fusion (half outside the
# slice), [2, 3) sort overlapping [2.5, 3.5) fusion, [6, 8) fusion, and
# [12, 13) after the slice.  Busy inside: 1 + 1.5 + 2 = 4.5 ms.  Modules
# start at 0.5 (outside), 2 and 6.
PROFILE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 0.5, 1.5), (2, 2, 1), (1, 2.5, 1), (1, 6, 2), (1, 12, 1)])}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_events([(3, 0.5, 1.5), (3, 2, 1.5), (3, 6, 2)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "sort.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_complete" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events([(1, 1, 10)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{trace.ANCHOR}" }} }}
}}
"""


def test_reduce_profile_on_a_small_trace():
    planes = trace.read_planes(jax.profiler.ProfileData.from_text_proto(
        PROFILE))
    assert planes["anchor"] == (1e6, 11e6)
    # the host was submitting during [3.6, 5.5) ms and polling in [9, 10)
    spans = [("submit", 3.6e6, 5.5e6), ("poll", 9e6, 10e6)]
    r = trace.reduce_profile(planes, spans)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.0045)
    assert r["idle_pct"] == pytest.approx(55.0)
    assert r["launches"] == 2
    assert r["device_ops"] == [["fusion.1", pytest.approx(0.004)],
                               ["sort.2", pytest.approx(0.001)]]
    # gaps: [3.5, 6) 2.5 ms, [8, 11) 3 ms, [1, ...) none (an op covers 1)
    assert r["idle_gaps"] == [["poll", pytest.approx(0.003)],
                              ["submit", pytest.approx(0.0025)]]
    assert r["idle_by_span"] == {"poll": pytest.approx(0.003),
                                 "submit": pytest.approx(0.0025)}


def test_gaps_no_span_covers_are_named_as_such():
    planes = trace.read_planes(jax.profiler.ProfileData.from_text_proto(
        PROFILE))
    r = trace.reduce_profile(planes, [])
    assert {n for n, _ in r["idle_gaps"]} == {trace.NO_SPAN}


def test_a_trace_without_device_work_or_anchor_is_refused():
    planes = {"devices": [{"ops": [], "modules": []}], "anchor": (0, 1)}
    with pytest.raises(ValueError):
        trace.reduce_profile(planes)
    with pytest.raises(ValueError):
        trace.reduce_profile({"devices": [], "anchor": None})


def test_union_merges_overlaps():
    assert trace.union_ns([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == \
        [(1, 4.5), (5, 6)]
