"""Pallas TPU kernel: fused small-k top-k selection with payload.

Used by the completion engine's merge points (beam leaf buffer, cached
per-node top-K lists, cross-shard merges): candidates live in a VMEM tile
and k rounds of (max, argmax, mask) extract the result without a full sort.
For k << C this is cheaper than bitonic-sorting the whole tile and keeps
everything in registers/VMEM.

Tie behaviour matches jax.lax.top_k: equal scores resolve to the lower
candidate index (argmax picks the first maximum).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -(2**31 - 1)


def select_topk(s, p, k: int):
    """k rounds of (max, first argmax, mask) over int32 [BQ, C] scores
    with an int32 payload -> (scores [BQ, k], payloads [BQ, k]).

    Written without gathers, scatters or an int32 argmax (none of which
    Mosaic lowers): the first maximum is the smallest column whose score
    equals the row max, its payload is read off with a one-hot masked
    sum, and the selected slot is masked to ``_NEG``.  Same picks, in
    the same order, as ``argmax`` + ``s.at[rows, best].set(_NEG)``."""
    bq, c = s.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, c), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    out_s = jnp.zeros((bq, k), jnp.int32)
    out_p = jnp.zeros((bq, k), jnp.int32)
    for j in range(k):
        best = jnp.max(s, axis=1, keepdims=True)
        first = jnp.min(jnp.where(s == best, col, c), axis=1, keepdims=True)
        hit = col == first
        pay = jnp.sum(jnp.where(hit, p, 0), axis=1, keepdims=True)
        out_s = jnp.where(slot == j, best, out_s)
        out_p = jnp.where(slot == j, pay, out_p)
        s = jnp.where(hit, _NEG, s)
    return out_s, out_p


def _kernel(s_ref, p_ref, os_ref, op_ref, *, k: int):
    os_ref[...], op_ref[...] = select_topk(
        s_ref[...].astype(jnp.int32), p_ref[...], k)


@functools.partial(jax.jit, static_argnames=("k", "block_b", "interpret"))
def topk_select(scores, payload, k: int, *, block_b: int = 8,
                interpret: bool):
    """scores int32[B, C], payload int32[B, C] -> (top_s[B,k], top_p[B,k])."""
    bsz, c = scores.shape
    grid = (bsz // block_b,)
    kernel = functools.partial(_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, c), lambda i: (i, 0)),
            pl.BlockSpec((block_b, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, k), jnp.int32),
            jax.ShapeDtypeStruct((bsz, k), jnp.int32),
        ],
        interpret=interpret,
    )(scores, payload)
