"""Query generators of the paper's §7.3 workload, copied from
``repro/data/strings.py`` as they are there: a dictionary string, up to
two dictionary-side substrings rewritten to a rule's query side, then a
prefix of 2-24 characters.  ``make_workload`` draws strings uniformly,
``make_zipf_queries`` by Zipf rank."""

from __future__ import annotations

import numpy as np


def make_workload(ds, n_queries: int, seed: int = 0,
                  min_len: int = 2, max_len: int = 24) -> list[str]:
    """Paper §7.3 workload: apply rules to dictionary strings (dict-side ->
    query-side rewriting), then take a prefix of the rewritten string."""
    rng = np.random.default_rng(seed)
    inv = {}  # dictionary-side rhs -> query-side lhs choices
    for lhs, rhs in ds.rules:
        inv.setdefault(rhs, []).append(lhs)
    rhs_keys = sorted(inv)
    queries = []
    n_strings = len(ds.strings)
    while len(queries) < n_queries:
        s = ds.strings[int(rng.integers(0, n_strings))]
        # rewrite up to 2 applicable dictionary-side substrings
        for _ in range(2):
            hits = [r for r in rhs_keys if r in s]
            if not hits or rng.random() < 0.3:
                break
            r = hits[int(rng.integers(0, len(hits)))]
            lhs = inv[r][int(rng.integers(0, len(inv[r])))]
            i = s.find(r)
            s = s[:i] + lhs + s[i + len(r):]
        ln = int(rng.integers(min_len, max_len + 1))
        q = s[:ln].rstrip()
        if q:
            queries.append(q)
    return queries


def make_zipf_queries(ds, n_queries: int, seed: int = 0,
                      a: float = 1.3, min_len: int = 2,
                      max_len: int = 24) -> list[str]:
    """Zipf-skewed prefix queries: real autocomplete traffic concentrates
    on hot strings, so strings are drawn by Zipf rank (parameter ``a``)
    instead of uniformly; rule rewriting and prefix truncation match
    :func:`make_workload`."""
    rng = np.random.default_rng(seed)
    inv = {}
    for lhs, rhs in ds.rules:
        inv.setdefault(rhs, []).append(lhs)
    rhs_keys = sorted(inv)
    n_strings = len(ds.strings)
    queries = []
    while len(queries) < n_queries:
        rank = min(int(rng.zipf(a)), n_strings) - 1
        s = ds.strings[rank]
        for _ in range(2):
            hits = [r for r in rhs_keys if r in s]
            if not hits or rng.random() < 0.3:
                break
            r = hits[int(rng.integers(0, len(hits)))]
            lhs = inv[r][int(rng.integers(0, len(inv[r])))]
            i = s.find(r)
            s = s[:i] + lhs + s[i + len(r):]
        ln = int(rng.integers(min_len, max_len + 1))
        q = s[:ln].rstrip()
        if q:
            queries.append(q)
    return queries
