"""Shape-static primitives shared by every engine phase.

These are the reference (pure-jnp) implementations of the operations the
:class:`~repro.core.engine.substrate.Substrate` protocol exposes as its
overridable seam: vectorized CSR binary search / child lookup and the
dedup-compaction that keeps locus frontiers canonical.  Substrates default
to these; a kernel-backed substrate overrides the batched entry points it
has tuned code for.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from repro.core.engine.structs import INT_MAX, NEG_ONE


# Open dispatch records (``record_dispatch``); ``note_path`` adds to each.
_RECORDS: list[set[str]] = []


def note_path(op: str, path: str) -> None:
    """Record that dispatch ``op`` took ``path`` (e.g. "walk", "jnp" or
    "beam", "pallas-streamed").  Called while a program is traced."""
    for rec in _RECORDS:
        rec.add(f"{op}={path}")


@contextlib.contextmanager
def record_dispatch():
    """Collect, as "op=path" strings, the substrate dispatches of every
    program traced inside the block.  A program already compiled is not
    traced again, so the record names the code of the programs this
    block built."""
    rec: set[str] = set()
    _RECORDS.append(rec)
    try:
        yield rec
    finally:
        _RECORDS[:] = [r for r in _RECORDS if r is not rec]


def resolve_sub(cfg, sub):
    """Substrate threading helper: explicit ``sub`` wins, else the registry
    entry named by ``cfg.substrate`` (late import: the registry module
    imports this one)."""
    if sub is not None:
        return sub
    from repro.core.engine.substrate import get_substrate
    return get_substrate(cfg.substrate)


def iters_for(n: int) -> int:
    return max(1, int(math.ceil(math.log2(max(n, 1) + 1))))


def lower_bound(arr: jax.Array, lo, hi, x, iters: int):
    """First index in [lo, hi) with arr[idx] >= x (vectorized, fixed iters)."""
    size = max(int(arr.shape[0]), 1)
    for _ in range(iters):
        cont = lo < hi
        mid = (lo + hi) >> 1
        v = arr[jnp.clip(mid, 0, size - 1)]
        go_right = v < x
        lo = jnp.where(cont & go_right, mid + 1, lo)
        hi = jnp.where(cont & ~go_right, mid, hi)
    return lo


def csr_child_lookup(ptr, chars, children, nodes, ch, iters: int):
    """children[nodes] labelled ch via binary search in each CSR row; -1 if
    absent. nodes may contain -1 entries (propagated)."""
    if int(chars.shape[0]) == 0:
        return jnp.full(jnp.broadcast_shapes(nodes.shape, jnp.shape(ch)),
                        NEG_ONE, jnp.int32)
    valid = nodes >= 0
    n = jnp.where(valid, nodes, 0)
    lo = ptr[n]
    hi = ptr[n + 1]
    pos = lower_bound(chars, lo, hi, ch, iters)
    size = max(int(chars.shape[0]), 1)
    found = (pos < hi) & (chars[jnp.clip(pos, 0, size - 1)] == ch) & valid & (ch >= 0)
    return jnp.where(found, children[jnp.clip(pos, 0, size - 1)], NEG_ONE)


def dedup_pad(vec: jax.Array, width: int):
    """Unique ids of vec (-1 = empty), first `width` kept (ascending id order).

    Returns (out[width] int32 with -1 pad, n_dropped int32).

    §Perf iteration: one sort + O(n) scatter compaction (rank = running
    count of kept) instead of the original sort-mask-sort — on TPU the
    second bitonic sort was the locus DP's hottest op."""
    big = jnp.where(vec < 0, INT_MAX, vec)
    s = jnp.sort(big)
    idx = jnp.arange(s.shape[0], dtype=jnp.int32)
    keep = (idx == 0) | (s != jnp.roll(s, 1))
    keep &= s != INT_MAX
    rank = jnp.cumsum(keep) - 1                       # position among kept
    n_uniq = (rank[-1] + 1).astype(jnp.int32)
    dst = jnp.where(keep & (rank < width), rank, width)  # width = drop slot
    out = jnp.full((width + 1,), NEG_ONE, jnp.int32)
    out = out.at[dst].set(s, mode="drop")
    out = jnp.where(out == INT_MAX, NEG_ONE, out)[:width]
    dropped = jnp.maximum(n_uniq - width, 0)
    return out, dropped
