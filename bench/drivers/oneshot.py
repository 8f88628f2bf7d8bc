"""The ``oneshot`` driver: one caller sending
``CompletionService.complete`` batches back to back (a closed loop),
cycling through a pool of batches into which the seed deals the
queries."""

from __future__ import annotations

import time

import numpy as np

from bench.loadgen import NO_TRACE, make_queries, seed_seq


class Driver:
    """One caller sending ``complete`` batches back to back."""

    def __init__(self, traffic: dict, ds, seed: int, seconds: float,
                 work_seed: int = 0):
        self.k = int(traffic["k"])
        self.batch = int(traffic["batch"])
        self.seconds = float(seconds)
        n_pool = int(traffic["pool_batches"])
        qs = make_queries(ds, traffic["queries"], self.batch * n_pool,
                          seed_seq(work_seed, 2))
        qs = [qs[i] for i in
              np.random.default_rng(seed_seq(seed, 2)).permutation(len(qs))]
        self.pool = [qs[j * self.batch:(j + 1) * self.batch]
                     for j in range(n_pool)]
        # which answered batches are kept for the check: the first pass
        # over the pool, then one in ``keep_every`` drawn from the seed
        self.keep_every = int(traffic["keep_every"])
        self._keep_rng = np.random.default_rng(seed_seq(seed, 3))

    def service(self, index):
        from repro.serving import CompletionService
        return CompletionService(index)

    def warm(self, svc) -> None:
        """One pass over the pool: the window sends these batches again,
        so every program it runs, exactness retries included, is then
        compiled."""
        for b in self.pool:
            svc.complete(b, k=self.k)

    def run(self, svc, tracer=NO_TRACE, clock=time.perf_counter):
        spans = tracer.spans
        pool, n_pool, k = self.pool, len(self.pool), self.k
        kept = []
        done = []
        keep = self._keep_rng
        t_win = clock()
        self.window_start = t_win
        tracer.arm(t_win)
        t_end = t_win + self.seconds
        self.n_done = n = 0
        while True:
            now = clock()
            if now >= t_end:
                break
            tracer.tick(now)
            ans = svc.complete(pool[n % n_pool], k=k)
            t1 = clock()
            spans.add("complete", now, t1)
            if n < n_pool or keep.random() * self.keep_every < 1.0:
                kept.append((n % n_pool, ans))
            done.append(t1)
            self.n_done = n = n + 1
        self.done = np.array(done)
        self.kept = kept
        self.window_end = done[-1] if done else t_end
        return t_win

    def resolved_times(self) -> np.ndarray:
        return np.repeat(self.done, self.batch)

    def outcomes(self):
        n = len(self.done) * self.batch
        return None, n, 0

    def metrics(self) -> dict:
        span = max(self.window_end - self.window_start, 1e-9)
        return {"answers_per_s": len(self.done) * self.batch / span}

    def report(self) -> list[str]:
        return [f"oneshot: {len(self.done)} batches of {self.batch} in "
                f"{self.window_end - self.window_start:.6f} s from a pool "
                f"of {len(self.pool)}; {len(self.kept)} batches kept for "
                f"the check"]

    def answers(self):
        for j, ans in self.kept:
            yield from zip(self.pool[j], ans)

    def counters(self, svc) -> dict:
        return {"batches": self.n_done}
