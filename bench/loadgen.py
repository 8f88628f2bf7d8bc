"""The general traffic generator: what every driver shares.

A traffic mix is a data file, ``bench/traffic/<mix>.json``, whose
``driver`` key names a driver, ``bench/drivers/<driver>.py`` (loaded
by name, as configurations and metric readers are), and whose
other keys are its parameters.  Each driver makes its whole schedule
before the window, drives the service through its public surface, keeps
what the window answered, and after the window hands the harness its
end-to-end metrics, counters and the (query, answer) pairs to check.

The work is the same for every seed: the multiset of queries is drawn
from the configuration's ``data_seed`` (``work_seed``), and ``--seed``
decides their order, the arrival times and the gaps between keys.
Drawn from ``--seed`` as well, the dictionary and the queries made
some seeds' runs cost two thirds more than others' (PERF.md).

A driver file holds one class, ``Driver(traffic, ds, seed, seconds,
work_seed)``, with ``service(index)``, ``warm(svc)``, ``run(svc,
tracer)`` (returns the window's start on the host clock and sets
``window_end``), ``outcomes()`` (-> ``(latencies or None, attempted,
failed)``), ``resolved_times()``, ``metrics()``, ``report()`` (lines
printed before the result), ``answers()`` (-> ``(query, answer)``
pairs to check) and ``counters(svc)`` (what the per-layer readers see).
"""

from __future__ import annotations

import gc
import resource
import time

import numpy as np

from bench import queries as query_gen

#: seconds a keystroke or batch may still take after the window closes
#: before it counts as never answered
GRACE_S = 60.0
#: a call into the service longer than this is listed as a stall
STALL_S = 0.05
#: the cgroup's CPU accounting (v2), read around stalls
CPU_STAT = "/sys/fs/cgroup/cpu.stat"


def seed_seq(seed: int, stream: int) -> np.random.SeedSequence:
    """An independent random stream ``stream`` of the run's seed."""
    return np.random.SeedSequence([int(seed), int(stream)])


def make_queries(ds, spec: dict, n: int, seed) -> list[str]:
    """``n`` queries by the generator a traffic file names: ``zipf``
    (``make_zipf_queries``) or ``uniform`` (``make_workload``)."""
    kw = dict(min_len=spec["min_len"], max_len=spec["max_len"])
    if spec["generator"] == "zipf":
        return query_gen.make_zipf_queries(ds, n, seed=seed, a=spec["a"],
                                           **kw)
    if spec["generator"] == "uniform":
        return query_gen.make_workload(ds, n, seed=seed, **kw)
    raise ValueError(f"unknown query generator {spec['generator']!r}")


def percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


class Spans:
    """Host spans kept in memory on the host clock while a trace runs:
    (name, start, end) in seconds of ``time.perf_counter``.  Off, it
    records nothing."""

    def __init__(self):
        self.on = False
        self.items: list[tuple[str, float, float]] = []

    def add(self, name: str, t0: float, t1: float) -> None:
        if self.on:
            self.items.append((name, t0, t1))


class _NoTrace:
    """Stands in for the tracer of an untraced run."""

    spans = Spans()

    def arm(self, start_at: float) -> None:
        pass

    def tick(self, now: float) -> None:
        pass


NO_TRACE = _NoTrace()


def cpu_stat() -> dict:
    """The cgroup's CPU counters (``nr_throttled``, ``throttled_usec``,
    ...), or ``{}`` where the file is not there."""
    try:
        with open(CPU_STAT) as f:
            return {k: int(v) for k, v in (ln.split() for ln in f)}
    except (OSError, ValueError):
        return {}


class Stalls:
    """What the host did during each call into the service that took
    longer than ``STALL_S``: this thread's CPU time, its page faults,
    Python's garbage-collection pauses, the flushes the call fired, and
    the cgroup's CPU throttling since the previous reading.  A stall
    whose CPU time is near its wall time was the host's own work; one
    with little CPU time waited: on the device, on a collection, on a
    fault, or on the CPU quota."""

    def __init__(self, t_win: float):
        self.t_win = t_win
        self.items: list[dict] = []
        self.gc_s = 0.0
        self.gc_by_gen = [0, 0, 0]
        self._gc_t0 = None
        self._stat = self.stat0 = cpu_stat()
        self.process_cpu0 = time.process_time()
        gc.callbacks.append(self._gc)
        self.mark()

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_by_gen[info["generation"]] += 1
            self._gc_t0 = None

    def close(self) -> None:
        """The readings' end (the driver's loop has ended)."""
        gc.callbacks.remove(self._gc)
        self.process_cpu = time.process_time() - self.process_cpu0
        self.stat1 = cpu_stat()

    def mark(self) -> None:
        """Readings before a call (once a loop turn)."""
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        self._cpu = time.thread_time()
        self._faults = (ru.ru_minflt, ru.ru_majflt)
        self._gc_mark = self.gc_s

    def record(self, name: str, t0: float, t1: float, flushes: int) -> None:
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        stat = cpu_stat()
        self.items.append({
            "call": name, "at_s": t0 - self.t_win, "ms": (t1 - t0) * 1e3,
            "cpu_ms": (time.thread_time() - self._cpu) * 1e3,
            "gc_ms": (self.gc_s - self._gc_mark) * 1e3,
            "minflt": ru.ru_minflt - self._faults[0],
            "majflt": ru.ru_majflt - self._faults[1],
            "flushes": flushes,
            "throttled": stat.get("nr_throttled", 0)
            - self._stat.get("nr_throttled", 0)})
        self._stat = stat

    def report(self, window_s: float) -> list[str]:
        d = {k: self.stat1.get(k, 0) - self.stat0.get(k, 0)
             for k in ("nr_periods", "nr_throttled", "throttled_usec")}
        worst = sorted(self.items, key=lambda x: -x["ms"])[:8]
        return [
            f"host over the driver's loop: process CPU "
            f"{self.process_cpu / window_s:.3f} cores, garbage collection "
            f"{self.gc_s * 1e3:.3f} ms in {self.gc_by_gen} collections by "
            f"generation; cgroup "
            + (f"periods {d['nr_periods']}, throttled {d['nr_throttled']} "
               f"for {d['throttled_usec'] / 1e3:.3f} ms" if self.stat0
               else "CPU accounting not readable"),
            f"calls into the service over {STALL_S * 1e3:.0f} ms, longest "
            f"8 of {len(self.items)} (call, s from the window's start, ms, "
            f"thread CPU ms, GC ms, minor/major faults, flushes, cgroup "
            f"throttlings since the last reading): "
            + ", ".join(f"({s['call']} {s['at_s']:.3f} {s['ms']:.1f} "
                        f"cpu {s['cpu_ms']:.1f} gc {s['gc_ms']:.1f} "
                        f"flt {s['minflt']}/{s['majflt']} "
                        f"fl {s['flushes']} thr {s['throttled']})"
                        for s in worst),
        ]
