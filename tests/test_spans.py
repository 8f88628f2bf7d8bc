"""The program's span recorder and the counters at its layer boundaries:
off, the recorder keeps nothing; on, every span knows its parent and its
request; the scheduler's queue wait and answer lag add up, per ticket, to
its latency; inexact rows count retry rounds; and every serving program
carries its name and its engine phases into the HLO it lowers to."""

import re

import jax
import numpy as np
import pytest

from repro.api import IndexSpec, build_index
from repro.core import make_rules
from repro.serving.scheduler import KeystrokeScheduler
from repro.spans import RECORDER, Spans

STRINGS = ["andrew pavlo", "andrew parker", "andrew packard",
           "william smith", "bill of rights"]
SCORES = [50, 40, 30, 20, 10]
RULES = [("andy", "andrew"), ("bill", "william")]
U = 2.0 ** -10          # a clock step that sums exactly (~0.98 ms)


@pytest.fixture(scope="module")
def idx():
    return build_index(STRINGS, SCORES, make_rules(RULES),
                       IndexSpec(kind="et", cache_k=4))


@pytest.fixture
def recording():
    RECORDER.clear()
    RECORDER.on = True
    yield RECORDER
    RECORDER.on = False
    RECORDER.clear()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _spans(rec):
    """[(name, parent name or None, rid, info)] in recording order."""
    out = []
    for (name, _, end), (parent, rid, info) in zip(rec.items, rec.links):
        assert end is not None, f"{name} left open"
        out.append((name, rec.items[parent][0] if parent >= 0 else None,
                    rid, info))
    return out


def test_spans_nest_by_thread_and_close_what_is_left_open():
    clock = FakeClock()
    sp = Spans(clock)
    sp.add("ignored", 0.0, 1.0)             # off: nothing kept
    assert sp.items == [] and sp.links == []
    sp.on = True
    outer = sp.begin("outer", 7)
    clock.t = 1.0
    inner = sp.begin("inner", 8)
    sp.add("leaf", 1.0, 1.5, 9)
    clock.t = 2.0
    sp.begin("left open")
    sp.end(inner, info="x")                 # closes "left open" too
    clock.t = 3.0
    sp.end(outer)
    assert sp.items == [("outer", 0.0, 3.0), ("inner", 1.0, 2.0),
                        ("leaf", 1.0, 1.5), ("left open", 2.0, 2.0)]
    assert sp.links == [(-1, 7, None), (outer, 8, "x"), (inner, 9, None),
                        (inner, None, None)]


def test_recorder_off_keeps_nothing(idx):
    RECORDER.clear()
    assert not RECORDER.on
    sched = KeystrokeScheduler(idx, block=2, max_wait_ms=1e6)
    s = sched.open(k=3)
    s.type("an")
    idx.complete(["bi"], k=3)
    assert RECORDER.items == [] and RECORDER.links == []


def test_recorder_on_links_parents_and_requests(idx, recording):
    clock = FakeClock()
    sched = KeystrokeScheduler(idx, block=2, max_wait_ms=2.0, clock=clock)
    a, b = sched.open(k=3), sched.open(k=3)
    a.submit(b"a")
    b.submit(b"b")                          # full: flush 1
    a.submit(b"n")
    clock.t += 0.01
    sched.pump()                            # deadline: flush 2 settles 1
    sched.drain()                           # settles flush 2
    calls = idx.lookups.calls
    idx.complete(["an", "bi"], k=3)
    spans = _spans(recording)
    flushes = [(rid, info) for name, _, rid, info in spans
               if name == "sched.flush"]
    assert flushes == [(1, ("full", 2)), (2, ("deadline", 1))]
    assert [(p, rid) for n, p, rid, _ in spans if n == "sched.dispatch"] \
        == [("sched.flush", 1)] * 2 + [("sched.flush", 2)] * 2
    assert [(p, rid) for n, p, rid, _ in spans if n == "sched.settle"] \
        == [("sched.flush", 1), (None, 2)]
    for child in ("sched.device_get", "sched.resolve"):
        assert [(p, rid) for n, p, rid, _ in spans if n == child] \
            == [("sched.settle", 1), ("sched.settle", 2)]
    index = [(n, p, rid) for n, p, rid, _ in spans if n.startswith("index.")]
    # padding to the query length, then to the compile-cache bucket
    assert index == [("index.complete", None, calls + 1),
                     ("index.pad", "index.complete", calls + 1),
                     ("index.pad", "index.complete", calls + 1),
                     ("index.fetch", "index.complete", calls + 1),
                     ("index.decode", "index.complete", calls + 1)]


def test_queue_wait_and_answer_lag_add_up_to_latency(idx):
    clock = FakeClock()
    sched = KeystrokeScheduler(idx, block=2, max_wait_ms=2.0, clock=clock)
    a, b = sched.open(k=3), sched.open(k=3)
    a1 = a.submit(b"a")                     # created 0
    clock.t = U
    b1 = b.submit(b"b")                     # full flush 1 at U
    clock.t = 2 * U
    a2 = a.submit(b"n")
    clock.t = 5 * U
    assert sched.pump() == 1                # deadline flush 2 at 5U settles 1
    clock.t = 7 * U
    sched.poll()                            # settles flush 2 at 7U
    st = sched.stats
    assert [t.consumed for t in (a1, b1, a2)] == [U, U, 5 * U]
    assert [t.latency_s for t in (a1, b1, a2)] == [5 * U, 4 * U, 5 * U]
    assert st.sum_queue_wait_s == U + 0 + 3 * U
    assert st.sum_answer_lag_s == 4 * U + 4 * U + 2 * U
    assert st.n_answered == 3 and st.sum_occupancy == 3
    for t in (a1, b1, a2):
        resolved = t.created + t.latency_s
        assert (t.consumed - t.created) + (resolved - t.consumed) \
            == t.latency_s
    assert st.mean_queue_wait_ms == pytest.approx(1e3 * 4 * U / 3)
    assert st.mean_answer_lag_ms == pytest.approx(1e3 * 10 * U / 3)


def test_dispatch_and_settle_wait_read_the_clock(idx):
    sched = KeystrokeScheduler(idx, block=2, max_wait_ms=1e6)
    sched.open(k=3).type("and")
    st = sched.stats
    assert st.n_flushes == 3
    assert st.dispatch_s > 0 and st.settle_wait_s > 0


def test_inexact_rows_count_retry_rounds(idx, recording):
    narrow = build_index(STRINGS, SCORES, make_rules(RULES),
                         IndexSpec(kind="et", frontier=4, gens=4, expand=2,
                                   max_steps=8))
    got = narrow.complete(["a", "b", "an"], k=3)
    assert got == idx.complete(["a", "b", "an"], k=3)
    lk = narrow.lookups
    assert (lk.calls, lk.queries, lk.retry_rounds, lk.retried_rows) == \
        (1, 3, 1, 3)
    assert lk.retries_per_1k == pytest.approx(1000 / 3)
    spans = _spans(recording)
    assert ("index.retry", "index.complete", 1, None) in spans
    assert ("index.fetch", "index.retry", 1, None) in spans


def _lowered(fn, *args):
    """The StableHLO text, with locations, of a bound jitted program."""
    return fn.func.lower(*fn.args, *args).as_text(debug_info=True)


def test_programs_carry_their_names_and_engine_scopes(idx):
    qs = np.full((8, 16), -1, np.int32)
    qlens = np.zeros((8,), np.int32)
    init, advance = idx._slab_fns(4)
    slab = jax.eval_shape(init)
    s_init, s_adv, s_topk = idx._session_fns(3)
    state = jax.eval_shape(s_init)
    texts = {
        "complete_batch": _lowered(idx._fn(8, 16, 3, idx.cfg), qs, qlens),
        "slab_advance": _lowered(advance, slab, np.full((4,), -1, np.int32),
                                 np.zeros((4,), bool)),
        "slab_topk": _lowered(idx._slab_topk_fn(4, 3), slab),
        "session_advance": _lowered(s_adv, state, np.int32(97)),
        "session_topk": _lowered(s_topk, state),
    }
    scopes = {"complete_batch": {"engine.walk", "engine.topk"},
              "slab_advance": {"engine.advance"},
              "slab_topk": {"engine.topk"},
              "session_advance": {"engine.advance"},
              "session_topk": {"engine.topk"}}
    for name, text in texts.items():
        assert f"module @jit_{name} " in text
        assert set(re.findall(r"engine\.[a-z]+", text)) == scopes[name]
