"""The ``typists`` driver: an open loop of typists behind a search box.

Typists arrive at uniform random times, ``typists_per_s`` of them for
each second of the run; each types one query a keystroke at a time, with
log-normal gaps between keys, through sessions of a
``CompletionService(batching=True)``.  A keystroke is timed from when it
was due to when its ticket resolved, so a stall charges every keystroke
that falls due behind it; a typist who finds every lane held waits for
one, and a keystroke the admission queue refuses waits in the
generator's backlog, both on the clock.
"""

from __future__ import annotations

import bisect
import collections
import time

import numpy as np

from bench.loadgen import (GRACE_S, NO_TRACE, STALL_S, Stalls, make_queries,
                           percentile, seed_seq)


class Driver:
    """Open-loop typists through ``CompletionService(batching=True)``."""

    def __init__(self, traffic: dict, ds, seed: int, seconds: float,
                 work_seed: int = 0):
        from repro.serving.scheduler import SchedulerOverloaded
        self._overloaded = SchedulerOverloaded
        self.k = int(traffic["k"])
        self.block = int(traffic["block"])
        self.max_wait_ms = float(traffic["max_wait_ms"])
        self.preroll = float(traffic["preroll_s"])
        self.seconds = float(seconds)
        self.max_len = int(traffic["queries"]["max_len"])
        rate = float(traffic["typists_per_s"])
        rng = np.random.default_rng(seed_seq(seed, 1))
        # rate x (pre-roll + window) typists at uniform random times: a
        # Poisson process given its count, so that every seed offers the
        # same load and only where it falls changes
        horizon = self.preroll + self.seconds
        arrive = np.sort(rng.uniform(-self.preroll, self.seconds,
                                     int(round(rate * horizon))))
        qs = make_queries(ds, traffic["queries"], len(arrive),
                          seed_seq(work_seed, 2))
        self.queries = [qs[i].encode() for i in rng.permutation(len(qs))]
        gap = traffic["gap_ms"]
        sigma2 = np.log1p(float(gap["cv"]) ** 2)
        mu = np.log(float(gap["mean"]) / 1e3) - sigma2 / 2
        lens = np.array([len(q) for q in self.queries], np.int64)
        self.offset = np.concatenate([[0], np.cumsum(lens)])
        n_keys = int(self.offset[-1])
        gaps = rng.lognormal(mu, np.sqrt(sigma2), n_keys)
        tid = np.repeat(np.arange(len(lens)), lens)
        pos = np.arange(n_keys) - self.offset[tid]
        gaps[pos == 0] = 0.0      # a typist's first key falls due on arrival
        # due time of each keystroke, in seconds from the window's start
        starts = np.cumsum(gaps)
        due = arrive[tid] + starts - starts[self.offset[tid]]
        self.due = due
        self.tid = tid
        self.pos = pos
        self.order = np.argsort(due, kind="stable")
        self.gaps_s = gaps[pos > 0]
        self.n_typists = len(lens)
        self.rate = rate
        self.grace_s = GRACE_S

    # -- service --------------------------------------------------------

    def service(self, index):
        from repro.serving import CompletionService
        return CompletionService(index, batching=True, block=self.block,
                                 max_wait_ms=self.max_wait_ms)

    def warm(self, svc) -> None:
        """Compile what this traffic runs: the slab programs at
        ``block`` lanes (one session typed through), and the one-shot
        path that resolves inexact lanes, at batch 1 and each length
        bucket a prefix of up to ``max_len`` bytes falls in."""
        sess = svc.open_session(k=self.k)
        t = None
        for ch in b"warm up":
            t = sess.submit(ch)
        while not t.done:
            svc.poll()
        sess.close()
        for n in sorted({min(8, self.max_len), min(16, self.max_len),
                         self.max_len}):
            svc.complete(["w" * n], k=self.k)

    # -- the window -----------------------------------------------------

    def run(self, svc, tracer=NO_TRACE, clock=time.perf_counter):
        """Drive the schedule; returns the window's start on ``clock``."""
        overloaded = self._overloaded
        spans = tracer.spans
        k, queries = self.k, self.queries
        due_l = self.due.tolist()
        tid_l = self.tid.tolist()
        pos_l = self.pos.tolist()
        last = [len(q) - 1 for q in queries]
        order = self.order.tolist()
        n_ev = len(order)
        sess = [None] * self.n_typists
        pend: list = [None] * self.n_typists
        queued = bytearray(self.n_typists)
        lane_wait: collections.deque = collections.deque()
        submit_q: collections.deque = collections.deque()
        outq: collections.deque = collections.deque()
        self.tickets = tickets = [None] * n_ev
        self.seen = seen = [0.0] * n_ev
        self.opened = opened = [0.0] * self.n_typists
        self.backlog = []
        backlog = 0       # keys seen due and not yet submitted
        due_sorted = [due_l[g] for g in order]
        n_sub = 0
        lanes_free = True
        ended = False
        t_win = clock() + self.preroll
        self.window_start = t_win
        # the scheduler's counts, to say how many flushes a slow call
        # fired
        sched = svc.scheduler.stats
        self.stalls = stalls = Stalls(t_win)
        tracer.arm(t_win)
        t_end = t_win + self.seconds
        t_give_up = t_end + self.grace_s
        next_sample = t_win
        i = 0
        while True:
            now = clock()
            tracer.tick(now)
            stalls.mark()
            if now >= next_sample and not ended:
                # keys due by the clock and not yet submitted, whether the
                # loop has seen them or not
                self.backlog.append(
                    bisect.bisect_right(due_sorted, now - t_win) - n_sub)
                next_sample += 1.0
            # keystrokes falling due join their typist's pending keys
            while i < n_ev and due_l[order[i]] + t_win <= now:
                g = order[i]
                if due_l[g] >= self.seconds:
                    i = n_ev
                    break
                i += 1
                tp = tid_l[g]
                seen[g] = now
                backlog += 1
                p = pend[tp]
                if p is None:
                    p = pend[tp] = collections.deque()
                    lane_wait.append(tp)
                p.append(g)
                if sess[tp] is not None and not queued[tp]:
                    queued[tp] = 1
                    submit_q.append(tp)
            if i == n_ev and not ended:
                ended = True
                # sessions with nothing left to type in the window free
                # their lanes for typists still waiting for one
                for tp, s in enumerate(sess):
                    if s is not None and not pend[tp]:
                        s.close()
                        sess[tp] = None
                        lanes_free = True
            while lane_wait and lanes_free:
                tp = lane_wait[0]
                t0 = clock()
                try:
                    s = svc.open_session(k=k)
                except overloaded:
                    lanes_free = False
                    break
                spans.add("open", t0, clock())
                lane_wait.popleft()
                sess[tp] = s
                opened[tp] = t0
                queued[tp] = 1
                submit_q.append(tp)
            for _ in range(len(submit_q)):
                tp = submit_q.popleft()
                p, s = pend[tp], sess[tp]
                done_typing = False
                try:
                    while p and clock() < t_give_up:
                        g = p[0]
                        f0 = sched.n_flushes
                        t0 = clock()
                        tickets[g] = t = s.submit(queries[tp][pos_l[g]])
                        t1 = clock()
                        spans.add("submit", t0, t1)
                        if t1 - t0 > STALL_S:
                            stalls.record("submit", t0, t1,
                                          sched.n_flushes - f0)
                        outq.append(t)
                        p.popleft()
                        backlog -= 1
                        n_sub += 1
                        done_typing = pos_l[g] == last[tp]
                except overloaded:
                    submit_q.appendleft(tp)
                    break
                queued[tp] = 0
                if done_typing or ended:
                    s.close()
                    sess[tp] = None
                    lanes_free = True
            f0 = sched.n_flushes
            t0 = clock()
            if svc.poll():
                lanes_free = True   # a flush completes deferred closes
            t1 = clock()
            if t1 - t0 > 20e-6:
                spans.add("poll", t0, t1)
                if t1 - t0 > STALL_S:
                    stalls.record("poll", t0, t1, sched.n_flushes - f0)
            while outq and outq[0].done:
                outq.popleft()
            if ended and not outq and not backlog:
                break
            if now > t_give_up:
                break
        stalls.close()
        self.loop_s = clock() - (t_win - self.preroll)
        self.window_end = t_end
        return t_win

    # -- after the window -----------------------------------------------

    def _in_window(self):
        return [g for g in range(len(self.due))
                if 0.0 <= self.due[g] < self.seconds]

    def outcomes(self):
        """(answered keystrokes' latencies in ms, attempted, failed)."""
        lat = []
        win = self._in_window()
        t_win = self.window_start
        for g in win:
            t = self.tickets[g]
            if t is not None and t.done:
                lat.append((t.created + t.latency_s - (t_win + self.due[g]))
                           * 1e3)
        return np.array(lat), len(win), len(win) - len(lat)

    def resolved_times(self) -> np.ndarray:
        """Host-clock times at which window keystrokes resolved."""
        return np.array([t.created + t.latency_s for t in self.tickets
                         if t is not None and t.done])

    def metrics(self) -> dict:
        lat, attempted, failed = self.outcomes()
        return {"keystroke_p50_ms": percentile(lat, 50),
                "keystroke_p90_ms": percentile(lat, 90),
                "keystroke_p99_ms": percentile(lat, 99)}

    def report(self) -> list[str]:
        lat, attempted, failed = self.outcomes()
        win = self._in_window()
        late = np.array([self.seen[g] - (self.window_start + self.due[g])
                         for g in win if self.seen[g]]) * 1e3
        waited = [self.opened[t] - (self.window_start + self.due[g])
                  for g in win if self.pos[g] == 0
                  for t in [self.tid[g]] if self.opened[t]]
        waited = np.array(waited) * 1e3
        backlog = self.backlog
        return [
            f"typists: {self.n_typists} scheduled at {self.rate} /s, "
            f"block {self.block}, max_wait_ms {self.max_wait_ms}; "
            f"inter-key gap mean {self.gaps_s.mean() * 1e3:.3f} ms, "
            f"p50 {np.median(self.gaps_s) * 1e3:.3f} ms, "
            f"p99 {np.percentile(self.gaps_s, 99) * 1e3:.3f} ms",
            f"keystrokes: {attempted} due in the window, {len(lat)} "
            f"answered (the latency sample), {failed} never answered; "
            f"latency from due time p50 {percentile(lat, 50):.4f} ms, p90 "
            f"{percentile(lat, 90):.4f} ms, p99 {percentile(lat, 99):.4f} "
            f"ms, max {lat.max(initial=0):.4f} ms",
            f"generator lateness (ms, seen minus due): p50 "
            f"{percentile(late, 50):.4f} p99 {percentile(late, 99):.4f} "
            f"max {late.max(initial=0):.4f}",
            f"lane wait (ms, lane opened minus first key due): p50 "
            f"{percentile(waited, 50) if len(waited) else 0:.4f} max "
            f"{waited.max(initial=0):.4f}",
            f"generator backlog (keys due by the clock, not yet submitted), "
            f"each second from the window's start: "
            f"{backlog}",
            *self.stalls.report(self.loop_s),
        ]

    def answers(self):
        """(prefix, answer) of every keystroke answered in the window."""
        for g in self._in_window():
            t = self.tickets[g]
            if t is not None and t.done:
                q = self.queries[self.tid[g]][:self.pos[g] + 1]
                yield q.decode(), t.results

    def counters(self, svc) -> dict:
        st = svc.scheduler.stats
        return {"flushes": st.n_flushes, "occupancy": st.sum_occupancy,
                "fallbacks": st.fallbacks, "block": self.block}
