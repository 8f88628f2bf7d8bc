"""Stateful incremental typing sessions.

A real autocomplete workload is per-keystroke: each query extends the
previous prefix by one char.  ``Session`` carries the engine's resumable
:class:`~repro.core.engine.LocusState` across keystrokes, so typing
``"Andy P"`` then ``"a"`` advances the existing locus frontier by one
char-step instead of re-running the full locus DP over the prefix.

A state snapshot is kept per typed char, so ``backspace()`` restores the
previous frontier without replay.  When the fixed-width frontier ever
overflowed (state inexact), top-k falls back to the one-shot
``index.complete`` path, which widens the search until exact.
"""

from __future__ import annotations

import numpy as np

import jax


def resolve_topk(index, scores, sids, exact, prefix: bytes, k: int):
    """Decode a session's device top-k into (score, string) pairs.

    When the result is inexact (frontier overflow or a failed beam bound)
    the widened one-shot ``index.complete`` path recovers exactness from
    the raw prefix — the single exactness contract shared by the
    sequential :class:`Session` and the batched scheduler demux.  Pending
    mutations take the same escape hatch: the compiled session top-k sees
    only the base epoch's tables, so the overlay-merged one-shot path
    answers from the raw prefix until the next ``compact()``."""
    if getattr(index, "has_mutations", False) or not bool(exact):
        return index.complete([bytes(prefix)], k=k)[0]
    return index._decode_row(scores, sids)


class Session:
    """Per-user incremental completion session over a CompletionIndex."""

    def __init__(self, index, k: int = 10):
        self.index = index
        self.k = k
        self._init, self._advance, self._topk = index._session_fns(k)
        self._prefix = bytearray()
        self._states = [jax.block_until_ready(self._init())]
        self._epoch = index.epoch

    def _sync_epoch(self) -> None:
        """Migrate onto the index's current epoch.

        After a hot-swap (``compact``) or ``reconfigure`` the compiled
        fns are bound to the previous epoch's tables/config, so
        refetch them and re-derive the whole per-char state history by
        replaying the retained prefix — the keystroke-boundary migration
        the epoch versioning exists for."""
        if self._epoch == self.index.epoch:
            return
        self._init, self._advance, self._topk = \
            self.index._session_fns(self.k)
        states = [self._init()]
        for byte in self._prefix:
            states.append(self._advance(states[-1], np.int32(byte)))
        jax.block_until_ready(states[-1])
        self._states = states
        self._epoch = self.index.epoch

    # -- typing ------------------------------------------------------------

    @property
    def prefix(self) -> str:
        return bytes(self._prefix).decode("utf-8", errors="replace")

    def type(self, text: str | bytes) -> list[tuple[int, str]]:
        """Append keystrokes and return the top-k for the new prefix."""
        self._sync_epoch()
        data = text.encode() if isinstance(text, str) else bytes(text)
        for byte in data:
            self._states.append(
                self._advance(self._states[-1], np.int32(byte)))
            self._prefix.append(byte)
        return self.topk()

    def backspace(self, n: int = 1) -> list[tuple[int, str]]:
        """Remove the last ``n`` *characters* (restores the saved
        frontier).

        The prefix is a byte string with one engine state per byte, but a
        user-facing backspace removes a codepoint: deleting single bytes
        would leave a dangling multi-byte UTF-8 head whose loci match
        nothing (and which ``prefix`` can't even render).  Each character
        removed pops its full byte run — a continuation byte is
        ``0b10xxxxxx``, so scanning back over them finds the head."""
        self._sync_epoch()
        nbytes = 0
        for _ in range(n):
            if nbytes >= len(self._prefix):
                break
            # skip the character's continuation bytes, then its head
            while nbytes < len(self._prefix) - 1 and \
                    0x80 <= self._prefix[len(self._prefix) - 1 - nbytes] \
                    < 0xC0:
                nbytes += 1
            nbytes += 1
        if nbytes:
            del self._states[len(self._states) - nbytes:]
            del self._prefix[len(self._prefix) - nbytes:]
        return self.topk()

    def reset(self) -> None:
        del self._states[1:]
        self._prefix.clear()

    # -- lookup ------------------------------------------------------------

    def topk(self, k: int | None = None) -> list[tuple[int, str]]:
        """Top-k (score, suggestion) pairs for the current prefix."""
        if k is not None and k != self.k:
            # different k: no compiled session fn for it; one-shot path
            return self.index.complete([bytes(self._prefix)], k=k)[0]
        self._sync_epoch()
        scores, sids, exact = jax.tree.map(
            np.asarray, self._topk(self._states[-1]))
        return resolve_topk(self.index, scores, sids, exact,
                            bytes(self._prefix), self.k)
