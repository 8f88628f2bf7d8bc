"""The comparison that decides ``correct`` catches what it is there to
catch.  Runs of both cells' traffic at a tiny size on the CPU, past the
harness's look for a chip, with the timed path broken underneath: a
session whose state never advances, half of a batch (or of the lanes)
left out, an answer altered where it is produced.  Each run must read
``correct`` false; the sound run true.  And so must the control (the
reference with the synonym rules left out, in the program's place)."""

import sys
import time
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.control import control_wrap  # noqa: E402
from bench.run import build, run_cell  # noqa: E402

SEED = 2**31 + 4242
CELLS = {
    "typists": harness.Cell(
        "tiny.typists", 1,
        {"name": "tiny-usps", "dataset": "usps", "n_strings": 800,
         "data_seed": 3, "spec": {"kind": "et", "substrate": "jnp"},
         "reference": "prefix_scan"},
        {"driver": "typists", "k": 10, "typists_per_s": 25.0,
         "gap_ms": {"mean": 60, "cv": 0.5}, "block": 16,
         "max_wait_ms": 2.0, "preroll_s": 0.3,
         "queries": {"generator": "zipf", "a": 1.3, "min_len": 2,
                     "max_len": 24}},
        [], [], harness.load_driver(ROOT, "typists")),
    "oneshot": harness.Cell(
        "tiny.oneshot", 1,
        {"name": "tiny-dblp", "dataset": "dblp", "n_strings": 800,
         "data_seed": 3, "spec": {"kind": "tt", "substrate": "jnp"},
         "reference": "prefix_scan"},
        {"driver": "oneshot", "k": 10, "batch": 16, "pool_batches": 4,
         "keep_every": 1,
         "queries": {"generator": "uniform", "min_len": 2, "max_len": 24}},
        [], [], harness.load_driver(ROOT, "oneshot")),
}


@pytest.fixture(scope="module")
def built():
    return {name: build(cell.config, log=lambda line: None)
            for name, cell in CELLS.items()}


def _run(name, built, wrap=None):
    return run_cell(CELLS[name], SEED, 0.6, False,
                    t_start=time.perf_counter(), service_wrap=wrap,
                    built=built[name], log=lambda line: None)


class Ticket:
    """A ticket whose answer is rewritten by ``alter(results)`` once the
    real one resolves."""

    def __init__(self, real, alter):
        self.real, self.alter = real, alter

    def __getattr__(self, name):
        return getattr(self.real, name)

    @property
    def done(self):
        return self.real.done

    @property
    def results(self):
        return self.alter(self.real.results)


class Service:
    """The real service with ``session_fault(session_no)`` rewriting each
    session's tickets and ``batch_fault(queries, answers)`` each batch."""

    def __init__(self, real, session_fault=None, batch_fault=None):
        self.real, self.n_sessions = real, 0
        self.session_fault = session_fault or (lambda n: lambda t: t)
        self.batch_fault = batch_fault or (lambda q, a: a)

    def __getattr__(self, name):
        return getattr(self.real, name)

    def open_session(self, k):
        real, alter = self.real.open_session(k), self.session_fault(
            self.n_sessions)
        self.n_sessions += 1
        return types.SimpleNamespace(
            submit=lambda ch: alter(real.submit(ch)), close=real.close)

    def complete(self, queries, k=10):
        return self.batch_fault(queries, self.real.complete(queries, k=k))


def frozen_state(n):
    """Every keystroke of a session answered as its first was: the
    session's state never advances."""
    first = []

    def alter(t):
        first.append(t) if not first else None
        return Ticket(t, lambda r: first[0].results)
    return alter


def half_the_lanes(n):
    """Every other session's answers left out (empty)."""
    return (lambda t: Ticket(t, lambda r: [])) if n % 2 else (lambda t: t)


def _altered(rows):
    return rows[:-1] + [(rows[-1][0], rows[-1][1] + " (altered)")] \
        if rows else rows


def altered_answer_session(n):
    return lambda t: Ticket(t, _altered)


def half_the_batch(queries, answers):
    h = len(answers) // 2
    return answers[:h] + [[] for _ in answers[h:]]


def altered_answer_batch(queries, answers):
    return [_altered(a) if i == 3 else a for i, a in enumerate(answers)]


def test_sound_runs_are_correct(built):
    for name in CELLS:
        result, _ = _run(name, built)
        assert result["correct"], (name, result["compared"])
        assert result["attempted"] > 20 and result["failed"] == 0


@pytest.mark.parametrize("fault", [frozen_state, half_the_lanes,
                                   altered_answer_session])
def test_typists_faults_read_incorrect(built, fault):
    result, _ = _run("typists", built,
                     lambda svc: Service(svc, session_fault=fault))
    assert not result["correct"]
    assert result["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault", [half_the_batch, altered_answer_batch])
def test_oneshot_faults_read_incorrect(built, fault):
    result, _ = _run("oneshot", built,
                     lambda svc: Service(svc, batch_fault=fault))
    assert not result["correct"]
    assert result["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("name", list(CELLS))
def test_the_control_reads_incorrect(built, name):
    """The control in the program's place, under the timed path: the
    harness's own verdict reads it incorrect."""
    cell = CELLS[name]
    wrap = control_wrap(built[name][0], cell.traffic["k"],
                        harness.reference(cell.config))
    result, _ = _run(name, built, wrap)
    assert not result["correct"]
    assert result["compared"]["wrong_answers"]["value"] > 0
    assert result["failed"] == 0
