"""The plain reference: top-k completion with synonym rules by a scan of
the sorted dictionary.

It has the semantics of the paper's Problem 1 (the program's
``OracleIndex``) by another route, and imports nothing of the program:
the distinct strings sorted (each at its highest score); the matches of
a query are the union of the sorted ranges that its rewritings are
prefixes of, where a rewriting replaces non-overlapping occurrences of
rules' query sides in the query by their dictionary sides; the answer is
the k best matches by score, then string.

``agrees`` holds an answer to the reference under the repository's tie
contract: the same top-k score list, the strings above the k-th score
exactly the reference's, and strings tied at the k-th score distinct
matches carrying that score (the engine breaks such ties in discovery
order, the reference by string).

Copied from the program's ``chip_smoke.py`` (``rewritings``,
``PrefixScan``, ``agrees``), with rules taken as (query side, dictionary
side) string pairs and a memo per query.
"""

from __future__ import annotations

import bisect

import numpy as np


def rewritings(q: bytes, rules) -> set[bytes]:
    """Every rewriting of ``q`` under the rules (non-overlapping lhs
    occurrences of the typed query replaced by their rhs): a string can
    match ``q`` only if one of these is a prefix of it."""
    reach: list[set[bytes]] = [set() for _ in range(len(q) + 1)]
    reach[0].add(b"")
    for pos in range(len(q)):
        for pre in reach[pos]:
            reach[pos + 1].add(pre + q[pos:pos + 1])
            for lhs, rhs in rules:
                if q.startswith(lhs, pos):
                    reach[pos + len(lhs)].add(pre + rhs)
    return reach[len(q)]


class PrefixScan:
    """Top-k completion by scanning the sorted dictionary.

    ``strings``: the dictionary; ``scores``: one non-negative integer per
    string; ``rules``: (query side, dictionary side) pairs of str."""

    def __init__(self, strings, scores, rules):
        pairs = sorted(zip((s.encode() for s in strings),
                           np.asarray(scores).tolist()))
        # a duplicate string keeps its highest score, the last of its run
        last = [i for i in range(len(pairs))
                if i + 1 == len(pairs) or pairs[i + 1][0] != pairs[i][0]]
        self.keys = [pairs[i][0] for i in last]
        self.scores = np.array([pairs[i][1] for i in last], np.int64)
        self.rules = [(lhs.encode(), rhs.encode()) for lhs, rhs in rules]
        self._rows: dict[str, np.ndarray] = {}

    def rows(self, q: str) -> np.ndarray:
        """Indices into ``keys`` of every match of ``q``, ascending."""
        rows = self._rows.get(q)
        if rows is None:
            ranges = [(bisect.bisect_left(self.keys, p),
                       # UTF-8 never holds 0xff, so p + 0xff bounds p's range
                       bisect.bisect_left(self.keys, p + b"\xff"))
                      for p in rewritings(q.encode(), self.rules)]
            rows = self._rows[q] = np.unique(np.concatenate(
                [np.arange(lo, hi) for lo, hi in ranges]
                or [np.zeros(0, int)]))
        return rows

    def complete(self, q: str, k: int) -> list[tuple[int, str]]:
        rows = self.rows(q)
        top = rows[np.lexsort((rows, -self.scores[rows]))[:k]]
        return [(int(self.scores[i]), self.keys[i].decode()) for i in top]

    def tie_ok(self, q: str, kth: int):
        """Whether a string may fill a place tied at score ``kth``: it
        must be a match of ``q`` carrying that score."""
        rows = self.rows(q)

        def ok(x: str) -> bool:
            i = bisect.bisect_left(self.keys, x.encode())
            j = np.searchsorted(rows, i)
            return (j < len(rows) and rows[j] == i
                    and self.keys[i] == x.encode() and self.scores[i] == kth)
        return ok

    def verdict(self, q: str, got, k: int) -> str | None:
        """``None`` when ``got`` answers ``q`` under the tie contract,
        else what differs."""
        want = self.complete(q, k)
        return agrees(want, got, self.tie_ok(q, want[-1][0] if want else 0))


def agrees(want, got, tie_ok) -> str | None:
    """``None`` when ``got`` meets the engine's contract with the
    reference answer ``want``, else what differs.

    The same top-k score list, and every suggestion a match carrying that
    score.  Among strings tied at the k-th score the engine may return
    any, so above the k-th score the strings must be the reference's
    exactly, and at it they must be distinct and pass ``tie_ok``."""
    if [s for s, _ in got] != [s for s, _ in want]:
        return f"scores {[s for s, _ in got]} != {[s for s, _ in want]}"
    kth = want[-1][0] if want else None
    if sorted(r for r in got if r[0] != kth) != \
            sorted(r for r in want if r[0] != kth):
        return f"strings above the k-th score: {got} != {want}"
    tied = [x for s, x in got if s == kth]
    if len(set(tied)) != len(tied) or not all(map(tie_ok, tied)):
        return f"strings tied at the k-th score {kth}: {tied}"
    return None


def build(strings, scores, rules) -> PrefixScan:
    """The reference over one configuration's data (the harness calls
    ``build`` of the module a configuration names)."""
    return PrefixScan(strings, scores, rules)
