"""Pallas TPU kernel: fused candidate scoring + running top-k.

The `retrieval_cand` shape (1 query x 1M candidates) and the trie shard
merge both reduce to "dot-score a big matrix against one vector, keep the
top-k". Materializing all scores to HBM and sorting wastes bandwidth; this
kernel tiles candidates into (BC, D) VMEM blocks, scores them on the MXU,
and maintains a running top-k in the output ref across grid steps (the
output block index map is constant, so it persists).

k rounds of (max, argmax, mask) per block keep selection in-VMEM; ids are
globalized with the grid index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -3.0e38  # python scalar: jnp constants would be captured as consts


def _kernel(q_ref, c_ref, os_ref, oi_ref, *, k: int, block_c: int):
    step = pl.program_id(0)
    q = q_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    scores = c @ q  # [BC] on the MXU

    @pl.when(step == 0)
    def _init():
        os_ref[...] = jnp.full((k,), _NEG, jnp.float32)
        oi_ref[...] = jnp.full((k,), -1, jnp.int32)

    run_s = os_ref[...]
    run_i = oi_ref[...]
    ids = step * block_c + jnp.arange(block_c, dtype=jnp.int32)
    cat_s = jnp.concatenate([run_s, scores])
    cat_i = jnp.concatenate([run_i, ids])
    # k rounds of extract-max; running entries sit first so that on equal
    # scores the earlier (lower-id) candidate wins, matching lax.top_k
    for j in range(k):
        best = jnp.argmax(cat_s)
        os_ref[j] = cat_s[best]
        oi_ref[j] = cat_i[best]
        cat_s = cat_s.at[best].set(_NEG)


@functools.partial(jax.jit, static_argnames=("k", "block_c", "interpret"))
def candidate_topk(query, candidates, k: int, *, block_c: int = 1024,
                   interpret: bool):
    """query float[D]; candidates float[C, D] (C divisible by block_c).

    Returns (scores[k] float32, ids[k] int32), score-descending.
    """
    cands, d = candidates.shape
    grid = (cands // block_c,)
    kernel = functools.partial(_kernel, k=k, block_c=block_c)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((block_c, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k,), lambda i: (0,)),
            pl.BlockSpec((k,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k,), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.int32),
        ],
        interpret=interpret,
    )(query, candidates)
