"""Continuous-batching keystroke scheduler for the completion hot path.

``CompletionService`` was call-in/answer-out: every keystroke of every
session paid its own device dispatch.  At serving scale the dispatch — not
the kernel — is the bottleneck, so this module makes the serving layer
itself the batcher, generalizing the vLLM-style ``SlotScheduler`` of
:mod:`repro.serving.engine` from the LM decode loop to the trie path:

- a :class:`KeystrokeScheduler` owns a fixed-shape *slab*: a stacked
  :class:`~repro.core.engine.LocusState` with ``block`` lanes (the jit
  shape).  Each open :class:`BatchSession` pins one lane;
- submitted keystrokes enter a bounded admission queue (per-lane FIFOs —
  a session's chars are sequentially dependent, so one flush consumes at
  most one keystroke per lane but coalesces keystrokes *across* lanes);
- a *flush* assembles one padded micro-batch block — chars[block] with
  ``-1`` for idle lanes, a reset mask folded into the same dispatch — and
  runs one batched ``advance_loci_batch`` step plus (when any consumed
  keystroke wants results) one batched ``topk_from_loci_batch``, then
  demuxes scores/sids per lane.  The demux is pipelined one flush deep:
  a flush dispatches its own device work first and then settles the
  *previous* flush's stashed handles, so the host-side decode overlaps
  device compute instead of leaving the device idle;
- flushes fire when every occupied lane has a keystroke queued (a *full*
  block) or when the oldest queued keystroke would exceed its latency
  budget (``max_wait_ms`` — a *deadline* flush of a partial block), or on
  an explicit :meth:`~KeystrokeScheduler.drain`;
- the admission queue is bounded (``max_queue``): a submit beyond it
  raises :class:`SchedulerOverloaded` so overload surfaces as
  backpressure at the edge instead of unbounded memory.

Per-lane results are bit-identical to replaying the same keystrokes
through a sequential :class:`repro.api.session.Session`: lanes never
interact inside the vmapped advance, the batched phase 2 is per-row, and
the inexact-result fallback goes through the same widened one-shot path
(:func:`repro.api.session.resolve_topk`).

The scheduler is cooperatively driven (no background thread — JAX
dispatch from one thread keeps flush order, and therefore latency
accounting, deterministic): ``submit`` auto-flushes full blocks,
``pump()`` fires deadline flushes, and blocking helpers
(``BatchSession.type``, ``Ticket.result``, ``drain``) flush until their
work resolves.  Throughput comes from many sessions in flight — a lone
blocking session degrades to sequential dispatch by construction.
"""

from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field

import numpy as np

import jax

from repro.api.session import resolve_topk
from repro.spans import RECORDER


class SchedulerOverloaded(RuntimeError):
    """Backpressure: the admission queue (or lane table) is full."""


@dataclass
class Ticket:
    """One keystroke in flight through the batcher."""

    lane: int
    char: int                     # byte value; -1 = reset-only flush filler
    want_topk: bool
    k: int
    created: float
    prefix: bytes = b""           # lane prefix *after* this keystroke —
                                  # snapshotted at submit because the
                                  # session may type further before the
                                  # flush that consumes this ticket lands
    reset_first: bool = False     # re-init the lane before the char step
    results: list | None = None   # (score, string) pairs once resolved
    done: bool = False
    consumed: float | None = None  # start of the flush that took it
    latency_s: float | None = None  # created -> resolved

    def result(self, scheduler: "KeystrokeScheduler") -> list:
        """Block (cooperatively) until this keystroke's flush lands."""
        while not self.done:
            scheduler.flush()
        return self.results


@dataclass
class BatchStats:
    """Flush accounting for one scheduler.

    The times are sums of the scheduler's clock, so a reader takes the
    difference of two readings and divides: a keystroke's latency
    (created -> resolved) is its queue wait (created -> consumed, the
    start of the flush that took it) plus its answer lag (consumed ->
    resolved)."""

    n_keystrokes: int = 0
    n_flushes: int = 0
    full_flushes: int = 0          # every occupied lane advanced
    deadline_flushes: int = 0      # fired by the latency budget
    rejected: int = 0              # submits refused by backpressure
    fallbacks: int = 0             # inexact lanes resolved via one-shot path
    sum_occupancy: int = 0         # lanes consumed across all flushes
    migrations: int = 0            # slab rebuilds onto a new index epoch
    sum_queue_wait_s: float = 0.0  # over every ticket a flush consumed
    sum_answer_lag_s: float = 0.0  # over result-bearing tickets resolved
    n_answered: int = 0            # result-bearing tickets resolved
    dispatch_s: float = 0.0        # host time in the advance/top-k calls
    settle_wait_s: float = 0.0     # host time blocked in device_get

    @property
    def mean_occupancy(self) -> float:
        return self.sum_occupancy / max(self.n_flushes, 1)

    @property
    def mean_queue_wait_ms(self) -> float:
        return 1e3 * self.sum_queue_wait_s / max(self.sum_occupancy, 1)

    @property
    def mean_answer_lag_ms(self) -> float:
        return 1e3 * self.sum_answer_lag_s / max(self.n_answered, 1)


class BatchSession:
    """One typing stream riding the scheduler's shared micro-batches.

    API-compatible with :class:`repro.api.session.Session` for the
    ``type``/``backspace``/``reset``/``topk``/``prefix`` surface, plus the
    non-blocking ``submit`` that makes cross-session coalescing possible.
    """

    def __init__(self, scheduler: "KeystrokeScheduler", lane: int, k: int):
        self.scheduler = scheduler
        self.lane = lane
        self.k = k
        self._prefix = bytearray()
        self._reset_pending = False
        self._closed = False

    @property
    def prefix(self) -> str:
        return bytes(self._prefix).decode("utf-8", errors="replace")

    @property
    def prefix_bytes(self) -> bytes:
        return bytes(self._prefix)

    # -- non-blocking path -------------------------------------------------

    def submit(self, char: int | bytes | str, want_topk: bool = True
               ) -> Ticket:
        """Enqueue one keystroke; returns its :class:`Ticket`.

        Raises :class:`SchedulerOverloaded` when the admission queue is
        full — callers shed load or flush and retry."""
        if self._closed:
            raise RuntimeError("session is closed")
        if isinstance(char, str):
            char = char.encode()
        if isinstance(char, (bytes, bytearray)):
            if len(char) != 1:
                raise ValueError("submit takes exactly one keystroke")
            char = char[0]
        # mutate only after admission: a backpressure rejection must leave
        # the session's prefix and reset flag exactly as they were
        prefix = bytes(self._prefix) + bytes([int(char)])
        ticket = self.scheduler._enqueue(
            self, int(char), want_topk, self._reset_pending, prefix)
        self._reset_pending = False
        self._prefix.append(int(char))
        return ticket

    # -- blocking Session-compatible surface -------------------------------

    def type(self, text: str | bytes) -> list[tuple[int, str]]:
        """Feed keystrokes and return the top-k for the new prefix.

        Each char is one scheduler ticket (chars of one session are
        sequentially dependent, so they ride consecutive flushes); the
        call blocks until the last one resolves."""
        data = text.encode() if isinstance(text, str) else bytes(text)
        if not data:
            return self.topk()
        tickets = [self.submit(bytes([b])) for b in data]
        return tickets[-1].result(self.scheduler)

    def topk(self, k: int | None = None) -> list[tuple[int, str]]:
        """Top-k for the current prefix (a reset-only/no-op flush when
        nothing is pending on this lane)."""
        if k is not None and k != self.k:
            return self.scheduler.index.complete(
                [bytes(self._prefix)], k=k)[0]
        ticket = self.scheduler._enqueue(self, -1, True,
                                         self._reset_pending,
                                         bytes(self._prefix))
        self._reset_pending = False
        return ticket.result(self.scheduler)

    def backspace(self, n: int = 1) -> list[tuple[int, str]]:
        """Remove the last ``n`` keystrokes.

        The slab holds only the newest frontier per lane (no per-keystroke
        history — that is the memory price of packing sessions into a
        fixed slab), so backspace replays the shortened prefix through the
        batch path."""
        kept = bytes(self._prefix[:max(len(self._prefix) - n, 0)])
        self.reset()
        if not kept:
            return self.topk()
        return self.type(kept)

    def reset(self) -> None:
        """Restart at the empty prefix.

        Free at submit time: the reset rides the next ticket's flush as a
        lane re-init mask folded into the same batched advance dispatch."""
        self._prefix.clear()
        self._reset_pending = True

    def close(self) -> None:
        """Release the lane back to the scheduler."""
        if not self._closed:
            self.scheduler._release(self)
            self._closed = True


class KeystrokeScheduler:
    """Admission queue + fixed-shape slab + micro-batch flush loop."""

    def __init__(self, index, *, block: int = 8, max_wait_ms: float = 2.0,
                 max_queue: int | None = None, on_keystroke=None,
                 clock=time.perf_counter):
        """index: a CompletionIndex (needs the slab entry points).
        block: lanes per slab = the fixed jit batch shape = max
            concurrent sessions.
        max_wait_ms: latency budget; a queued keystroke older than this
            triggers a partial-block deadline flush on the next
            submit/pump.
        max_queue: admission-queue bound across all lanes (default
            ``4 * block``); beyond it submits raise SchedulerOverloaded.
        on_keystroke: optional callable(latency_seconds) invoked per
            resolved result-bearing keystroke (the service's stats hook).
        clock: injectable monotonic clock (tests drive deadlines with a
            fake one)."""
        if block < 1:
            raise ValueError("block must be >= 1")
        self.index = index
        self.block = block
        self.max_wait_ms = max_wait_ms
        self.max_queue = 4 * block if max_queue is None else max_queue
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self.on_keystroke = on_keystroke
        self.clock = clock
        self.stats = BatchStats()
        self._init_fn, self._advance_fn = index._slab_fns(block)
        self._slab = jax.block_until_ready(self._init_fn())
        self._topk_fns: dict[int, object] = {}   # k -> jitted slab top-k
        # one stashed flush of un-demuxed results: [(k, tickets, device
        # handles)] — settled after the NEXT flush's dispatch so host-side
        # decode overlaps device compute (see _flush)
        self._unsettled: list | None = None
        self._lanes: list[BatchSession | None] = [None] * block
        self._queues: list[collections.deque[Ticket]] = [
            collections.deque() for _ in range(block)]
        self._draining = [False] * block
        # per-lane prefix as of the last *consumed* ticket (== what the
        # slab's frontier actually encodes — the session's own _prefix
        # runs ahead of it by whatever is still queued); this is the
        # replay source for epoch migration
        self._consumed: list[bytes] = [b""] * block
        self._epoch = index.epoch
        self._pending = 0
        # O(1) mirrors of _ready_lanes()/_occupied() for the per-submit
        # pump hot path (scanning every lane per keystroke is measurable)
        self._n_ready = 0
        self._n_occupied = 0

    # -- sessions ----------------------------------------------------------

    def open(self, k: int = 10) -> BatchSession:
        """Pin a free lane to a new session (its state starts at the
        slab's empty-prefix init, so no device work is needed here)."""
        for lane, owner in enumerate(self._lanes):
            if owner is None:
                session = BatchSession(self, lane, k)
                # a recycled lane may carry the previous owner's frontier;
                # re-init rides the first ticket's flush like reset()
                session._reset_pending = True
                self._lanes[lane] = session
                self._consumed[lane] = b""
                self._n_occupied += 1
                return session
        raise SchedulerOverloaded(
            f"all {self.block} lanes are held by open sessions; close "
            f"one or build the scheduler with a larger block")

    def _release(self, session: BatchSession) -> None:
        # deferred release: in-flight keystrokes keep riding normal
        # flushes (forcing partial flushes here would collapse occupancy
        # every time a session ends); the lane frees once its queue
        # empties, and meanwhile it stops counting toward the full-flush
        # condition via _occupied
        if self._queues[session.lane]:
            self._draining[session.lane] = True
        else:
            self._lanes[session.lane] = None
            self._consumed[session.lane] = b""
            self._n_occupied -= 1

    # -- admission ---------------------------------------------------------

    def _enqueue(self, session: BatchSession, char: int, want_topk: bool,
                 reset_first: bool, prefix: bytes) -> Ticket:
        if self._lanes[session.lane] is not session:
            raise RuntimeError("session does not own its lane (closed?)")
        if self._pending >= self.max_queue:
            self.stats.rejected += 1
            raise SchedulerOverloaded(
                f"admission queue full ({self._pending} pending >= "
                f"max_queue={self.max_queue}); drain or shed load")
        ticket = Ticket(lane=session.lane, char=char, want_topk=want_topk,
                        k=session.k, created=self.clock(), prefix=prefix,
                        reset_first=reset_first)
        self._queues[session.lane].append(ticket)
        if len(self._queues[session.lane]) == 1:
            self._n_ready += 1
        self._pending += 1
        self.pump()
        return ticket

    # -- flush machinery ---------------------------------------------------

    @property
    def pending(self) -> int:
        """Queued keystrokes not yet consumed by a flush."""
        return self._pending

    def _occupied(self) -> int:
        return sum(o is not None for o in self._lanes)

    def _ready_lanes(self) -> list[int]:
        return [i for i, q in enumerate(self._queues) if q]

    def _oldest_age_ms(self, now: float) -> float:
        heads = [q[0].created for q in self._queues if q]
        return (now - min(heads)) * 1e3 if heads else 0.0

    def pump(self, now: float | None = None) -> int:
        """Fire due flushes: full blocks immediately, partial blocks once
        the oldest queued keystroke ages past ``max_wait_ms``.  Returns
        the number of flushes fired.  Drivers interleaving many sessions
        call this in their event loop; ``submit`` calls it internally."""
        fired = 0
        while self._pending:
            # "full" = every occupied lane has a keystroke queued: waiting
            # longer cannot raise this flush's occupancy (each lane
            # contributes at most one char), so fire immediately
            if self._n_ready > 0 and self._n_ready == self._n_occupied:
                self._flush(kind="full")
                fired += 1
                continue
            now_ = self.clock() if now is None else now
            if self._oldest_age_ms(now_) >= self.max_wait_ms:
                self._flush(kind="deadline")
                fired += 1
                continue
            break
        return fired

    def poll(self, now: float | None = None) -> int:
        """Non-blocking driver tick: :meth:`pump`, plus idle settling.

        ``pump`` alone starves the pipeline's tail — the last flush's
        results stay stashed (computed on device, never demuxed) until
        another flush or an explicit ``flush()``/``drain()``, so a driver
        looping on ``pump()`` and checking ``ticket.done`` spins forever
        once the queue empties.  ``poll`` settles the stash as soon as
        nothing is queued, making it the one call an event loop needs."""
        fired = self.pump(now)
        if not self._pending:
            self._settle()
        return fired

    def flush(self) -> None:
        """Force one partial-block flush (drain/result paths); settles
        stashed results when nothing is queued."""
        if self._pending:
            self._flush(kind="forced")
        else:
            self._settle()

    def drain(self) -> None:
        """Flush until no keystroke is queued or awaiting demux."""
        while self._pending:
            self._flush(kind="forced")
        self._settle()

    def _flush(self, kind: str) -> None:
        if self._epoch != self.index.epoch:
            self._migrate()
        st = self.stats
        rec = RECORDER
        rid = st.n_flushes + 1
        fl = rec.begin("sched.flush", rid) if rec.on else -1
        start = self.clock()
        # one ticket per lane, FIFO within the lane
        taken: list[Ticket] = []
        chars = np.full((self.block,), -1, np.int32)
        resets = np.zeros((self.block,), bool)
        for lane in self._ready_lanes():
            t = self._queues[lane].popleft()
            t.consumed = start
            st.sum_queue_wait_s += start - t.created
            taken.append(t)
            chars[lane] = t.char
            resets[lane] = t.reset_first
            # t.prefix is the lane prefix after this keystroke (resets
            # included), which is exactly what the slab encodes once this
            # flush's advance lands
            self._consumed[lane] = t.prefix
            if not self._queues[lane]:
                self._n_ready -= 1
                if self._draining[lane]:
                    self._lanes[lane] = None   # deferred close completes
                    self._draining[lane] = False
                    self._consumed[lane] = b""
                    self._n_occupied -= 1
        self._pending -= len(taken)
        t0 = self.clock()
        self._slab = self._advance_fn(self._slab, chars, resets)
        now = self.clock()
        st.dispatch_s += now - t0
        if fl >= 0:
            rec.add("sched.dispatch", t0, now, rid)
        st.n_flushes += 1
        st.sum_occupancy += len(taken)
        st.n_keystrokes += sum(t.char >= 0 for t in taken)
        if kind == "full":
            st.full_flushes += 1
        elif kind == "deadline":
            st.deadline_flushes += 1
        for t in taken:
            if not t.want_topk:     # advance-only keystrokes resolve here
                t.done = True
                t.latency_s = now - t.created
        # pipeline: dispatch this flush's top-k (one batched call per
        # distinct k — usually one; jax dispatch is async so these return
        # device handles immediately), stash it, and only then settle the
        # *previous* flush — its device_get is nearly free by now and the
        # host-side demux/decode runs while this flush computes on device
        prev = self._unsettled
        self._unsettled = None
        wanting = [t for t in taken if t.want_topk]
        if wanting:
            by_k: dict[int, list[Ticket]] = {}
            for t in wanting:
                by_k.setdefault(t.k, []).append(t)
            stash = []
            for k, tickets in sorted(by_k.items()):
                topk_fn = self._topk_fns.get(k)
                if topk_fn is None:
                    topk_fn = self._topk_fns[k] = \
                        self.index._slab_topk_fn(self.block, k)
                t0 = self.clock()
                handles = topk_fn(self._slab)
                t1 = self.clock()
                st.dispatch_s += t1 - t0
                if fl >= 0:
                    rec.add("sched.dispatch", t0, t1, rid)
                stash.append((rid, k, tickets, handles))
            self._unsettled = stash
        if prev:
            self._settle_handles(prev)
        if fl >= 0:
            rec.end(fl, (kind, len(taken)))

    def _migrate(self) -> None:
        """Rebuild the slab on the index's current epoch (hot-swap /
        reconfigure migration at the flush boundary).

        The stashed flush settles first — its handles are plain device
        arrays computed on the old tables, still valid to demux.  Then
        the slab fns are refetched (the swap cleared the compile cache /
        the reconfigure changed the cfg key) and every lane's *consumed*
        prefix is replayed column-wise: one batched advance per step of
        the longest prefix, idle lanes riding as -1 no-ops.  Queued
        keystrokes are untouched — they consume from the rebuilt slab on
        the flushes that follow, so nothing is lost or reordered."""
        self._settle()
        self._init_fn, self._advance_fn = self.index._slab_fns(self.block)
        self._topk_fns = {}
        slab = self._init_fn()
        no_reset = np.zeros((self.block,), bool)
        for step in range(max(map(len, self._consumed), default=0)):
            chars = np.full((self.block,), -1, np.int32)
            for lane, p in enumerate(self._consumed):
                if step < len(p):
                    chars[lane] = p[step]
            slab = self._advance_fn(slab, chars, no_reset)
        self._slab = jax.block_until_ready(slab)
        self._epoch = self.index.epoch
        self.stats.migrations += 1

    def _settle(self) -> None:
        """Resolve the stashed flush, if any (the pipeline's tail)."""
        prev = self._unsettled
        self._unsettled = None
        if prev:
            self._settle_handles(prev)

    def _settle_handles(self, stash) -> None:
        # each stashed entry holds the full [block, k] slab result of
        # flush ``rid``; the lanes wanting that k are picked out
        st = self.stats
        rec = RECORDER
        se = rec.begin("sched.settle", stash[0][0]) if rec.on else -1
        for rid, k, tickets, handles in stash:
            t0 = self.clock()
            scores, sids, exact = jax.device_get(handles)
            t1 = self.clock()
            st.settle_wait_s += t1 - t0
            rs = -1
            if se >= 0:
                rec.add("sched.device_get", t0, t1, rid)
                rs = rec.begin("sched.resolve", rid)
            for t in tickets:
                if not bool(exact[t.lane]):
                    st.fallbacks += 1
                t.results = resolve_topk(
                    self.index, scores[t.lane], sids[t.lane],
                    exact[t.lane], t.prefix, k)
                t.done = True
                now = self.clock()
                t.latency_s = now - t.created
                st.sum_answer_lag_s += now - t.consumed
                st.n_answered += 1
                if self.on_keystroke is not None:
                    self.on_keystroke(t.latency_s)
            if rs >= 0:
                rec.end(rs)
        if se >= 0:
            rec.end(se)
