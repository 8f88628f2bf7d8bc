"""Pallas TPU kernel: EmbeddingBag (ragged gather + segment reduce).

JAX has no native nn.EmbeddingBag; the recsys substrate needs one for its
multi-hot sparse features. The ops.py wrapper densifies the ragged
(indices, offsets) batch to [B, max_bag] (pad = -1), and this kernel blocks
bags into VMEM tiles, gathers rows of the (VMEM-resident) table and
reduces over the bag dimension. Per-sample weights fold into the gather.

On real hardware the table tile would be streamed per-shard (row-sharded
tables over the `model` axis, cf. DESIGN §6); gathering from a VMEM tile is
exactly the per-shard inner kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(tab_ref, idx_ref, w_ref, out_ref, *, mode: str):
    tab = tab_ref[...]
    idx = idx_ref[...]
    w = w_ref[...]
    v = tab.shape[0]
    valid = idx >= 0
    rows = jnp.take(tab, jnp.clip(idx, 0, v - 1), axis=0)  # [BB, MB, D]
    rows = rows * w[..., None]
    rows = jnp.where(valid[..., None], rows, 0.0)
    out = rows.sum(axis=1)
    if mode == "mean":
        cnt = valid.sum(axis=1).astype(tab.dtype)
        out = out / jnp.maximum(cnt, 1)[:, None]
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("mode", "block_b", "interpret"))
def embedding_bag_dense(table, idx, weights, *, mode: str = "sum",
                        block_b: int = 128, interpret: bool):
    """table float[V, D]; idx int32[B, MB] (-1 pad); weights float[B, MB]."""
    bsz, mb = idx.shape
    v, d = table.shape
    grid = (bsz // block_b,)
    kernel = functools.partial(_kernel, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((v, d), lambda i: (0, 0)),
            pl.BlockSpec((block_b, mb), lambda i: (i, 0)),
            pl.BlockSpec((block_b, mb), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, d), table.dtype),
        interpret=interpret,
    )(table, idx, weights)
