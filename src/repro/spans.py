"""The process's span recorder: named host intervals at the program's
layer boundaries, kept in memory while someone is looking.

``RECORDER`` is the one recorder of the process, as a profiler is: the
spans of every index (an overlay's side index included), scheduler and
caller land on one timeline, nested as the calls were.  The program
marks its boundaries in it (``sched.*`` in the keystroke scheduler,
``index.*`` in ``CompletionIndex``), and a caller may mark its own calls
into the program in the same list.  Each span keeps its name, its start
and end in seconds of ``time.perf_counter``, its parent (the span open
on this thread when it began, or ``-1``) and a request id (the
scheduler's flush number, the index's call number).  A caller's span
opened with ``begin`` is the parent of the program's spans inside it;
one recorded after the call with ``add`` only covers them in time.

Off is the default, and off costs one attribute check per site: a site
reads ``RECORDER.on`` and, when it is false, reads no clock and
allocates nothing::

    i = RECORDER.begin("index.fetch", rid) if RECORDER.on else -1
    ...
    if i >= 0:
        RECORDER.end(i)

A span begun while the recorder was on is ended even if it was turned
off meanwhile, so nothing is left open.
"""

from __future__ import annotations

import threading
import time


class Spans:
    """Spans in memory: ``items[i]`` is ``(name, start, end)`` and
    ``links[i]`` is ``(parent, rid, info)``; an open span's end is
    ``None``, and ``info`` is what the site adds at the end (the
    scheduler's flush kind and lanes taken), else ``None``."""

    def __init__(self, clock=time.perf_counter):
        self.on = False
        self.clock = clock
        self.items: list[tuple[str, float, float | None]] = []
        self.links: list[tuple[int, int | None, object]] = []
        self._local = threading.local()

    def _stack(self) -> list[int]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _parent(self) -> int:
        st = self._stack()
        return st[-1] if st else -1

    def add(self, name: str, t0: float, t1: float,
            rid: int | None = None) -> None:
        """A span whose two ends the caller read; its parent is the span
        open on this thread.  Does nothing while the recorder is off."""
        if self.on:
            self.items.append((name, t0, t1))
            self.links.append((self._parent(), rid, None))

    def begin(self, name: str, rid: int | None = None) -> int:
        """Open a span now, as a child of the span open on this thread;
        returns its index for :meth:`end`."""
        i = len(self.items)
        self.items.append((name, self.clock(), None))
        self.links.append((self._parent(), rid, None))
        self._stack().append(i)
        return i

    def end(self, i: int, info=None) -> None:
        """Close span ``i`` (and any span opened inside it and left
        open, so that an exception cannot leave the stack skewed),
        keeping ``info`` with it."""
        t = self.clock()
        st = self._stack()
        while st:
            j = st.pop()
            name, t0, _ = self.items[j]
            self.items[j] = (name, t0, t)
            if j == i:
                if info is not None:
                    parent, rid, _ = self.links[i]
                    self.links[i] = (parent, rid, info)
                break

    def clear(self) -> None:
        self.items.clear()
        self.links.clear()
        self._stack().clear()


#: the process's recorder
RECORDER = Spans()
