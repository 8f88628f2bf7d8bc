"""Bounded, bucketed cache for jit-compiled engine entry points.

The old ``CompletionIndex._compiled`` dict grew one entry per exact
(batch, length, k, cfg) tuple — unbounded under production traffic where
batch sizes drift.  Here shapes are first *bucketed* (batch and query
length rounded up to powers of two) so nearby shapes share an executable,
and the executables live in an LRU with a fixed capacity so a long-lived
serving process cannot accumulate compilations without bound.

:func:`enable_persistent_cache` is the one place that points JAX's
on-disk compilation cache somewhere, so a fresh process reuses the
executables an earlier one compiled.
"""

from __future__ import annotations

import os
from collections import OrderedDict
from pathlib import Path
from typing import Callable, Hashable

import jax

#: The persistent cache's directory when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed, git-ignored path in the checkout.  A cache directory
#: that moved between runs (a temp, pid or time-based path) would never
#: be hit again.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that
    directory and this sets no other; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.  Entry points call it once at start-up
    (``repro.launch.serve``, ``chip_smoke.py``, the benchmarks); library
    code never does, so importing the package leaves JAX's configuration
    alone."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def bucket_size(n: int, minimum: int = 8) -> int:
    """Round ``n`` up to the next power of two (at least ``minimum``)."""
    n = max(int(n), 1)
    return max(minimum, 1 << (n - 1).bit_length())


class CompileCache:
    """LRU over compiled callables, keyed by hashable shape/config keys."""

    def __init__(self, maxsize: int = 32):
        if maxsize <= 0:
            raise ValueError("maxsize must be positive")
        self.maxsize = maxsize
        self._entries: OrderedDict[Hashable, object] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: Hashable, factory: Callable[[], object]):
        """Return the cached value for ``key``, building it via ``factory``
        on a miss (evicting the least-recently-used entry when full)."""
        try:
            value = self._entries[key]
            self._entries.move_to_end(key)
            self.hits += 1
            return value
        except KeyError:
            self.misses += 1
        value = factory()
        self._entries[key] = value
        if len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1
        return value

    def __len__(self) -> int:
        return len(self._entries)

    def keys(self) -> list:
        """The cached keys, least recently used first."""
        return list(self._entries)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._entries

    def clear(self) -> None:
        self._entries.clear()

    def stats(self) -> dict:
        return {"size": len(self._entries), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}
