"""The benchmark's generators and drivers: data and schedules are fixed
by the seed, and the open loop times each keystroke from when it fell
due, so a stall is charged to every keystroke that falls due behind it."""

import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, loadgen  # noqa: E402
from bench.datasets import dblp, usps  # noqa: E402

Typists = harness.load_driver(ROOT, "typists")
OneShot = harness.load_driver(ROOT, "oneshot")

SEED = 2**31 + 99
TYPISTS = {"driver": "typists", "k": 10, "typists_per_s": 40.0,
           "gap_ms": {"mean": 240, "cv": 0.5}, "block": 8,
           "max_wait_ms": 2.0, "preroll_s": 0.5,
           "queries": {"generator": "zipf", "a": 1.3, "min_len": 2,
                       "max_len": 24}}
ONESHOT = {"driver": "oneshot", "k": 10, "batch": 8, "pool_batches": 3,
           "keep_every": 2,
           "queries": {"generator": "uniform", "min_len": 2, "max_len": 24}}


@pytest.fixture(scope="module")
def ds():
    return usps.make(3000, seed=loadgen.seed_seq(SEED, 0))


@pytest.mark.parametrize("name", ["usps-1m-et", "dblp-25k-tt"])
def test_datasets_make_the_published_rules(name):
    """As many distinct rules as the source publishes, each applying to
    some string of the dictionary."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    ds = harness.dataset(cfg).make(3000, seed=loadgen.seed_seq(SEED, 0))
    assert len(set(ds.rules)) == len(ds.rules) == cfg["rules_published"]
    text = "\n".join(ds.strings)
    assert all(rhs in text for _, rhs in ds.rules)


@pytest.mark.parametrize("make", [usps.make, dblp.make])
def test_datasets_are_fixed_by_the_seed(make):
    a = make(1500, seed=loadgen.seed_seq(SEED, 0))
    b = make(1500, seed=loadgen.seed_seq(SEED, 0))
    c = make(1500, seed=loadgen.seed_seq(SEED + 1, 0))
    assert a.strings == b.strings and np.array_equal(a.scores, b.scores)
    assert a.rules == b.rules
    assert a.strings != c.strings


def test_schedules_are_fixed_by_the_seed(ds):
    a = Typists(TYPISTS, ds, SEED, 3.0)
    b = Typists(TYPISTS, ds, SEED, 3.0)
    c = Typists(TYPISTS, ds, SEED + 1, 3.0)
    assert a.queries == b.queries and np.array_equal(a.due, b.due)
    assert not np.array_equal(a.due[:50], c.due[:50])
    # another seed types the same queries in another order
    assert a.queries != c.queries and sorted(a.queries) == sorted(c.queries)
    # one key per byte of each query, each typist's keys in order
    assert len(a.due) == sum(map(len, a.queries))
    for t in range(a.n_typists):
        d = a.due[a.offset[t]:a.offset[t + 1]]
        assert np.all(np.diff(d) > 0)
    o1 = OneShot(ONESHOT, ds, SEED, 1.0)
    o2 = OneShot(ONESHOT, ds, SEED, 1.0)
    o3 = OneShot(ONESHOT, ds, SEED + 1, 1.0)
    assert o1.pool == o2.pool and len(o1.pool) == 3
    assert all(len(b) == 8 for b in o1.pool)
    assert o1.pool != o3.pool
    assert sorted(sum(o1.pool, [])) == sorted(sum(o3.pool, []))


def test_gaps_and_arrivals_follow_the_traffic_file(ds):
    t = Typists(dict(TYPISTS, typists_per_s=200.0), ds, SEED, 20.0)
    assert abs(t.gaps_s.mean() - 0.240) < 0.01
    assert abs(t.gaps_s.std() / t.gaps_s.mean() - 0.5) < 0.05
    first = t.due[t.offset[:-1]]
    assert len(first) == 200 * 20.5
    assert first.min() >= -0.5 and first.max() < 20.0
    # arrivals spread evenly over the run: each half gets about half
    assert abs((first < 10.0).mean() - 10.5 / 20.5) < 0.03


class FakeClock:
    """Advances 0.1 ms at every reading; ``stall`` jumps it."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-4
        return self.t


class FakeService:
    """Resolves every submitted keystroke at the next poll; the first
    poll at or after ``stall_at`` takes ``stall`` seconds."""

    def __init__(self, clock, stall_at=None, stall=0.0):
        self.clock, self.stall_at, self.stall = clock, stall_at, stall
        self.queue = []
        self.scheduler = types.SimpleNamespace(
            stats=types.SimpleNamespace(n_flushes=0))

    def open_session(self, k):
        svc = self

        def submit(ch):
            t = types.SimpleNamespace(created=svc.clock(), done=False,
                                      latency_s=None, results=None)
            svc.queue.append(t)
            return t
        return types.SimpleNamespace(submit=submit, close=lambda: None)

    def poll(self):
        if self.stall_at is not None and self.clock.t >= self.stall_at:
            self.clock.t += self.stall
            self.stall_at = None
        for t in self.queue:
            t.done, t.results = True, []
            t.latency_s = self.clock() - t.created
        n, self.queue = len(self.queue), []
        self.scheduler.stats.n_flushes += n > 0
        return n


def drive(ds, stall_at_s=None, stall=0.0):
    clock = FakeClock()
    drv = Typists(TYPISTS, ds, SEED, 2.0)
    start = clock.t + 1e-4 + TYPISTS["preroll_s"]
    svc = FakeService(clock, None if stall_at_s is None
                      else start + stall_at_s, stall)
    assert abs(drv.run(svc, clock=clock) - start) < 1e-3
    return drv


def test_open_loop_times_from_due_times(ds):
    drv = drive(ds)
    lat, attempted, failed = drv.outcomes()
    in_window = (drv.due >= 0) & (drv.due < 2.0)
    assert attempted == int(in_window.sum()) > 50 and failed == 0
    # with nothing in the way, an answer comes within a few clock ticks
    # of its due time
    assert lat.min() >= 0.0 and lat.max() < 5.0


def test_a_stall_is_charged_to_the_keystrokes_due_behind_it(ds):
    stall_at, stall = 1.0, 0.3
    drv = drive(ds, stall_at, stall)
    lat, attempted, failed = drv.outcomes()
    assert failed == 0
    due = drv.due[(drv.due >= 0) & (drv.due < 2.0)]
    assert len(lat) == len(due)
    # every keystroke that fell due during the stall waits for its end
    during = (due >= stall_at + 0.01) & (due < stall_at + stall - 0.01)
    assert during.sum() > 5
    want = (stall_at + stall - due[during]) * 1e3
    assert np.all(lat[during] >= want - 5.0)
    assert lat.max() >= 0.9 * stall * 1e3
    # and those due well after it are back to a few ticks
    assert np.all(lat[due > stall_at + stall + 0.1] < 5.0)
    # the slow call is listed, with what the host did during it
    (slow,) = drv.stalls.items
    assert slow["call"] == "poll" and slow["ms"] >= stall * 1e3
    assert abs(slow["at_s"] - stall_at) < 0.01
    assert {"cpu_ms", "gc_ms", "minflt", "majflt", "flushes",
            "throttled"} <= set(slow)
    assert any(line.startswith("calls into the service over")
               for line in drv.report())
