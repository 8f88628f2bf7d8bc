"""Pallas TPU kernel: fused cached-top-K locus gather + merge.

Phase 2b of the completion engine with a materialized per-node top-K
cache: every query's locus antichain (up to F nodes) owns a score-sorted
top-K list; the answer is the top-k of their union.  The pure-jnp path
gathers [B, F, K] score/sid tiles to HBM, reshapes, and runs a full
lax.top_k — this kernel keeps the whole thing in VMEM: the (small,
per-shard) cache tables are VMEM-resident like the trie-walk CSR tables,
the gather is a vectorized dynamic load of F*K candidates per query, and
k rounds of (max, argmax, mask) extract the result without materializing
or sorting the union.

Candidate order is loci-major / K-minor and ties resolve to the first
maximum, so results are bit-identical to lax.top_k over the same
flattening (the jnp reference in kernels/ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.topk_select import select_topk


def _kernel(loci_ref, ts_ref, ti_ref, os_ref, op_ref, *, k: int):
    loci = loci_ref[...]                  # [BB, F]
    ts = ts_ref[...]                      # [N, K]
    ti = ti_ref[...]
    bb, f = loci.shape
    n_nodes, kk = ts.shape
    valid = loci >= 0
    n = jnp.where(valid, loci, 0)
    offs = jnp.arange(kk, dtype=jnp.int32)
    flat_idx = (n[:, :, None] * kk + offs[None, None, :]).reshape(bb, f * kk)
    sc = jnp.take(ts.reshape(-1), flat_idx)       # vectorized VMEM gather
    si = jnp.take(ti.reshape(-1), flat_idx)
    mask = jnp.repeat(valid, kk, axis=1)          # loci-major, K-minor
    sc = jnp.where(mask, sc, -1)                  # -1 = empty (as in jnp)
    si = jnp.where(mask, si, -1)
    os_ref[...], op_ref[...] = select_topk(sc, si, k)   # ties: first max


@functools.partial(jax.jit, static_argnames=("k", "block_b", "interpret"))
def locus_topk_merge(loci, topk_score, topk_sid, k: int, *, block_b: int = 8,
                     interpret: bool):
    """loci int32[B, F] (-1 padded, B divisible by block_b; wrapper in
    ops.py pads); topk_score/topk_sid int32[N, K] ->
    (scores[B, k], sids[B, k]), score-descending, -1 where empty."""
    bsz, f = loci.shape
    n_nodes, kk = topk_score.shape
    grid = (bsz // block_b,)
    kernel = functools.partial(_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, f), lambda i: (i, 0)),
            pl.BlockSpec((n_nodes, kk), lambda i: (0, 0)),
            pl.BlockSpec((n_nodes, kk), lambda i: (0, 0)),
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
    )(loci, topk_score, topk_sid)
