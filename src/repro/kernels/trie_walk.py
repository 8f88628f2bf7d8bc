"""Pallas TPU kernel: batched longest-prefix trie walk.

The hot inner loop of every completion lookup (paper Alg. 2/4 locus search).
Queries are blocked into VMEM tiles of (BQ, L); the CSR tables
(first_child / edge_char / edge_child) are VMEM-resident — the sharding
story of the distributed index (§DESIGN 2.5) keeps per-shard sub-tries
small enough for this. Each of the L steps performs a vectorized
binary search over each query's current CSR row (fixed `iters` rounds,
no data-dependent control flow).

TPU adaptation notes: on a CPU/GPU this is pointer chasing; here it is a
fixed-depth loop of vector gathers (dynamic VMEM loads), which the VPU
executes without divergence.

Two tiers share the walk semantics:

- the *resident* kernel (``trie_walk``) holds the CSR tables whole in
  VMEM — fastest when the per-shard sub-trie fits the budget;
- the *streamed* kernel (``trie_walk_streamed``) keeps the tables in HBM
  and, per step, double-buffers each query's pointer pair and child-row
  window into VMEM scratch via ``make_async_copy``
  (:mod:`repro.kernels.stream`), so shard size is no longer VMEM-bound.
  The tile-aligned layout (``trie_build.pack_stream_tiles``) guarantees
  one window covers any node's whole child row, which makes the
  in-window binary search probe exactly what the resident kernel's
  global search probes — results are bit-identical.

``PallasSubstrate.can_walk_batch`` picks the tier by comparing the table
bytes against the configured VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stream import StreamTable, stream_csr_children


def _kernel(fc_ref, ec_ref, echild_ref, q_ref, qlen_ref, node_ref, depth_ref,
            *, iters: int, seq_len: int):
    fc = fc_ref[...]
    ec = ec_ref[...]
    echild = echild_ref[...]
    q = q_ref[...]
    qlen = qlen_ref[:, 0]
    bq = q.shape[0]
    e = ec.shape[0]

    def step(i, carry):
        node, matched = carry
        c = q[:, i]
        lo = jnp.take(fc, node)
        hi = jnp.take(fc, node + 1)
        for _ in range(iters):  # branch-free binary search (lower bound)
            cont = lo < hi
            mid = (lo + hi) >> 1
            v = jnp.take(ec, jnp.clip(mid, 0, e - 1))
            go_right = v < c
            lo = jnp.where(cont & go_right, mid + 1, lo)
            hi = jnp.where(cont & ~go_right, mid, hi)
        pos = jnp.clip(lo, 0, e - 1)
        found = (lo < jnp.take(fc, node + 1)) & (jnp.take(ec, pos) == c)
        active = (matched == i) & (i < qlen) & (c >= 0)
        take = found & active
        node = jnp.where(take, jnp.take(echild, pos), node)
        matched = jnp.where(take, matched + 1, matched)
        return node, matched

    node0 = jnp.zeros((bq,), jnp.int32)
    node, matched = jax.lax.fori_loop(0, seq_len, step, (node0, node0))
    node_ref[:, 0] = node
    depth_ref[:, 0] = matched


def _walk_call(kernel, tables, table_specs, queries, qlens, scratch, *,
               block_q: int, interpret: bool):
    # qlens / node / depth ride as [B, 1] columns: Mosaic tiles a rank-1
    # block only in multiples of 128 rows, a [block_q, 1] column
    # whenever block_q is a multiple of 8 (or the whole batch)
    bsz, seq_len = queries.shape
    col = pl.BlockSpec((block_q, 1), lambda i: (i, 0))
    node, depth = pl.pallas_call(
        kernel,
        grid=(bsz // block_q,),
        in_specs=table_specs + [
            pl.BlockSpec((block_q, seq_len), lambda i: (i, 0)), col],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((bsz, 1), jnp.int32)] * 2,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*tables, queries, qlens.reshape(bsz, 1))
    return node[:, 0], depth[:, 0]


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def trie_walk(first_child, edge_char, edge_child, queries, qlens,
              *, block_q: int = 128, interpret: bool):
    """Deepest locus node + matched depth for each query.

    queries: int32[B, L] (-1 padded), B divisible by block_q (wrapper in
    ops.py pads). Returns (node[B], depth[B]).
    """
    bsz, seq_len = queries.shape
    n1 = first_child.shape[0]
    e = max(edge_char.shape[0], 1)
    iters = max(1, (e).bit_length())
    if edge_char.shape[0] == 0:
        return jnp.zeros((bsz,), jnp.int32), jnp.zeros((bsz,), jnp.int32)
    kernel = functools.partial(_kernel, iters=iters, seq_len=seq_len)
    table_specs = [pl.BlockSpec((n1,), lambda i: (0,)),
                   pl.BlockSpec((e,), lambda i: (0,)),
                   pl.BlockSpec((e,), lambda i: (0,))]
    return _walk_call(kernel, [first_child, edge_char, edge_child],
                      table_specs, queries, qlens, [], block_q=block_q,
                      interpret=interpret)


def _stream_kernel(fc_hbm, ec_hbm, echild_hbm, q_ref, qlen_ref,
                   node_ref, depth_ref,
                   pair_buf, wc_buf, wk_buf, sem_p, sem_c, sem_k, *,
                   tile: int, iters: int, seq_len: int):
    q = q_ref[...]
    qlen = qlen_ref[:, 0]
    bq = q.shape[0]
    fc_t = StreamTable(fc_hbm, pair_buf, sem_p, 2)
    ec_t = StreamTable(ec_hbm, wc_buf, sem_c, tile)
    ek_t = StreamTable(echild_hbm, wk_buf, sem_k, tile)

    def step(i, carry):
        node, matched = carry
        c = q[:, i]
        # nodes are always live (the walk starts at the root and only
        # ever descends), so the child lookup needs no -1 masking; the
        # ch >= 0 guard inside matches the resident kernel's `active`
        child = stream_csr_children(fc_t, ec_t, ek_t, node, c, iters)
        take = (child >= 0) & (matched == i) & (i < qlen)
        node = jnp.where(take, child, node)
        matched = jnp.where(take, matched + 1, matched)
        return node, matched

    node0 = jnp.zeros((bq,), jnp.int32)
    node, matched = jax.lax.fori_loop(0, seq_len, step, (node0, node0))
    node_ref[:, 0] = node
    depth_ref[:, 0] = matched


@functools.partial(jax.jit, static_argnames=("tile", "block_q", "interpret"))
def trie_walk_streamed(first_child, edge_char, edge_child, queries, qlens,
                       *, tile: int, block_q: int = 8, interpret: bool):
    """HBM-resident variant of :func:`trie_walk`: same contract, same
    results, but the CSR tables stay in HBM and each step's pointer pairs
    and child-row windows are DMA-streamed into VMEM scratch.  ``tile``
    is the static window width from the tile-aligned layout
    (``EngineConfig.walk_tile``)."""
    bsz, seq_len = queries.shape
    if edge_char.shape[0] == 0:
        return jnp.zeros((bsz,), jnp.int32), jnp.zeros((bsz,), jnp.int32)
    iters = max(1, tile.bit_length())
    kernel = functools.partial(_stream_kernel, tile=tile, iters=iters,
                               seq_len=seq_len)
    hbm = pl.BlockSpec(memory_space=pltpu.ANY)
    scratch = [
        pltpu.VMEM((block_q, 2), jnp.int32),     # pointer-pair stage
        pltpu.VMEM((block_q, tile), jnp.int32),  # char-row windows
        pltpu.VMEM((block_q, tile), jnp.int32),  # child-row windows
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    return _walk_call(kernel, [first_child, edge_char, edge_child],
                      [hbm] * 3, queries, qlens, scratch, block_q=block_q,
                      interpret=interpret)
