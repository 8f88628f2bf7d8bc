"""Pallas TPU kernel: fused beam phase 2 (generator-pool priority search).

The paper's top-k priority search (engine/beam.py): every locus becomes a
lazy generator over its score-sorted emission list; each step pops the
best P emissions across all generators, folds leaves into the result heap,
re-arms popped generators, and keeps the best W of the merged pool by
admissible bound.  The pure-jnp path runs this as a vmapped data-dependent
``lax.while_loop`` whose every step is a chain of XLA ``top_k`` / gather /
scatter ops; this kernel keeps the whole per-query search state resident
in VMEM scratch for the entire search:

- the ``(W,)`` generator pool — node ids ``gn``, emission cursors ``gc``,
  admissible bounds ``gb``;
- the ``(k,)`` result heap (``ls`` scores / ``li`` string ids);
- the ``dropped_max`` exactness tracker (max bound ever dropped by the
  width-bounded pool — the admissible-bound exactness guard of the paper's
  §2.2 retry).

The data-dependent while_loop becomes a **masked fixed-trip loop** bounded
by the static ``max_steps``: ``lax.fori_loop`` runs exactly ``max_steps``
trips and every state write is predicated on the per-query ``active``
flag (the reference loop's own continuation condition), so rows that
finish early freeze bit-exactly where the while_loop would have stopped
them.  Each P-wide ``lax.top_k`` pop — and the k-wide leaf merge and
W-wide pool re-selection — is replaced by an in-kernel **bitonic
selection network**: one lexicographic sort over (bound desc, column
asc) pairs, which reproduces ``lax.top_k`` ordering exactly
(score-descending, ties to the lower index) and lowers to a single
bitonic network on the VPU instead of ``top_k``'s gather/scatter chain.

The search body is written once against a small emission-table accessor
seam and runs in two tiers:

- *resident* (``beam_topk_batch``): the emission tables (``emit_ptr`` /
  ``emit_node`` / ``emit_score`` / ``emit_is_leaf``) and ``leaf_sid``
  are VMEM-resident like the trie-walk kernel's CSRs;
- *streamed* (``beam_topk_batch_streamed``): the tables stay in HBM and
  each step's pointer pairs, emission-row windows and sid gathers are
  double-buffered into VMEM scratch via ``make_async_copy``
  (:mod:`repro.kernels.stream`).  The tile-aligned layout
  (``trie_build.pack_stream_tiles``) guarantees one ``emit_tile`` window
  covers any node's whole emission row, so reading the cursor slot off
  the streamed row yields exactly the resident gather's value — both
  tiers are bit-identical to ``jax.vmap(engine.beam.beam_topk)``
  (scores, string ids AND the per-query ``exact`` flags); the substrate
  parity suite enforces this in interpret mode on CPU.

``PallasSubstrate.can_beam_batch`` probes the static sizes (W, P, k,
max_steps) and picks the tier by comparing the emission-table bytes
against the VMEM budget; shapes outside the envelope fall back to the
vmapped jnp reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stream import StreamTable, row_take

# plain python int: jnp scalars would be captured as constants by the
# pallas kernel tracer
_NEG_ONE = -1


def _topk_sorted(vals, n: int, payloads):
    """``lax.top_k(vals, n)`` with payloads, as one bitonic selection
    network over [BQ, C].

    A single lexicographic sort on the key pair (-value, column index) —
    ascending on the negated value = descending on the value, with ties
    resolved toward the lower column index — reproduces ``lax.top_k``
    ordering exactly.  Returns (top_vals [BQ, n],
    top_idx [BQ, n], [top_payload [BQ, n], ...], residue_vals
    [BQ, C-n]): the residue is the sorted tail of *unselected* values
    (the pool re-selection reads the dropped bounds off it).  Values must
    stay above INT32_MIN (scores are >= -1 here), so the key negation
    cannot overflow.
    """
    bq, c = vals.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (bq, c), 1)
    out = jax.lax.sort((-vals, idx) + tuple(payloads), dimension=1,
                       num_keys=2, is_stable=False)
    svals = -out[0]
    return (svals[:, :n], out[1][:, :n],
            [p[:, :n] for p in out[2:]], svals[:, n:])


# ---------------------------------------------------------------------------
# emission-table accessor seam: the search body is tier-agnostic
# ---------------------------------------------------------------------------


class _ResidentEmit:
    """VMEM-resident emission-table reads (the original kernel's forms)."""

    def __init__(self, eptr, enode, escore, eleaf, lsid):
        self.eptr, self.enode = eptr, enode
        self.escore, self.eleaf, self.lsid_arr = escore, eleaf, lsid
        self.e_size = max(int(enode.shape[0]), 1)

    def emit_bound(self, nodes, cursors):
        """Admissible bound of each generator's current emission; -1 when
        the node is dead or the cursor ran off its emission list."""
        valid = nodes >= 0
        n = jnp.where(valid, nodes, 0)
        e = jnp.take(self.eptr, n) + cursors
        ok = valid & (e < jnp.take(self.eptr, n + 1))
        score = jnp.take(self.escore, jnp.clip(e, 0, self.e_size - 1))
        return jnp.where(ok, score, _NEG_ONE)

    def pop_emissions(self, nodes, cursors):
        """(node, score, is_leaf) of each generator's current emission
        (callers mask invalid lanes; a popped lane's cursor is in-row)."""
        e = jnp.take(self.eptr, nodes) + cursors
        e = jnp.clip(e, 0, self.e_size - 1)
        return (jnp.take(self.enode, e), jnp.take(self.escore, e),
                jnp.take(self.eleaf, e) != 0)

    def lsid(self, nodes):
        return jnp.take(self.lsid_arr, nodes)


class _StreamedEmit:
    """HBM-resident emission tables behind double-buffered windowed DMA.

    Pointer pairs stream per lane; emission rows stream as whole
    ``emit_tile`` windows (the tile covers the longest row) with the
    cursor slot read row-locally — the same value the resident gather
    reads at ``eptr[n] + cursor``.
    """

    def __init__(self, eptr_t, enode_t, escore_t, eleaf_t, lsid_t,
                 tile: int):
        self.eptr_t, self.enode_t = eptr_t, enode_t
        self.escore_t, self.eleaf_t, self.lsid_t = escore_t, eleaf_t, lsid_t
        self.tile = tile

    def emit_bound(self, nodes, cursors):
        valid = nodes >= 0
        n = jnp.where(valid, nodes, 0)
        lo, hi = self.eptr_t.pairs(n)
        ok = valid & (lo + cursors < hi)
        win = self.escore_t.windows(lo)
        cur = jnp.clip(cursors, 0, self.tile - 1)
        score = row_take(win, cur[..., None])[..., 0]
        return jnp.where(ok, score, _NEG_ONE)

    def pop_emissions(self, nodes, cursors):
        lo, _ = self.eptr_t.pairs(nodes)
        cur = jnp.clip(cursors, 0, self.tile - 1)
        node = row_take(self.enode_t.windows(lo), cur[..., None])[..., 0]
        score = row_take(self.escore_t.windows(lo), cur[..., None])[..., 0]
        leaf = row_take(self.eleaf_t.windows(lo), cur[..., None])[..., 0]
        return node, score, leaf != 0

    def lsid(self, nodes):
        return self.lsid_t.gather(nodes)


def _pk_iters(size: int) -> int:
    """Fixed trip count that lets a binary search converge over a
    ``size``-entry sorted table."""
    return max(1, int(math.ceil(math.log2(max(size, 1) + 1))))


def _pk_rank(ids, nodes, iters: int):
    """Sorted-id-table rank (clipped) + exact flag; mirrors
    ``engine.packed._rank`` as a fixed-trip binary search."""
    size = int(ids.shape[0])
    lo = jnp.zeros_like(nodes)
    hi = jnp.full_like(nodes, size)
    for _ in range(iters):
        cont = lo < hi
        mid = (lo + hi) >> 1
        v = jnp.take(ids, jnp.clip(mid, 0, max(size, 1) - 1))
        go_right = v < nodes
        lo = jnp.where(cont & go_right, mid + 1, lo)
        hi = jnp.where(cont & ~go_right, mid, hi)
    rc = jnp.clip(lo, 0, max(size, 1) - 1)
    return rc, (lo < size) & (jnp.take(ids, rc) == nodes)


class _PackedEmit:
    """Compressed-layout emission reads: stored nodes (``c_ids`` rows)
    read their compacted emission lists; an unstored (unary non-terminal
    dict) node's list is exactly ``[(v+1, max_score, not-leaf)]``, read
    off its chain representative — the same forms as
    :mod:`repro.core.engine.packed`.  Narrow (u8/u16) values widen to
    i32 at the read."""

    _IS_SYN = 4   # p_flags bit (mirror engine.packed)

    def __init__(self, flags, c_ids, eptr, enode, escore, eleaf,
                 maxscore, l_ids, l_sid):
        self.flags, self.c_ids, self.eptr = flags, c_ids, eptr
        self.enode, self.escore, self.eleaf = enode, escore, eleaf
        self.maxscore, self.l_ids, self.l_sid = maxscore, l_ids, l_sid
        self.e_size = max(int(enode.shape[0]), 1)

    def emit_bound(self, nodes, cursors):
        valid = nodes >= 0
        n = jnp.where(valid, nodes, 0)
        rc, stored = _pk_rank(self.c_ids, n,
                              _pk_iters(int(self.c_ids.shape[0])))
        e = jnp.take(self.eptr, rc) + cursors
        ok_s = stored & (e < jnp.take(self.eptr, rc + 1))
        sc_s = jnp.take(self.escore,
                        jnp.clip(e, 0, self.e_size - 1)).astype(jnp.int32)
        fl = jnp.take(self.flags, n).astype(jnp.int32)
        derived = ~stored & ((fl & self._IS_SYN) == 0) & (cursors == 0)
        ms = jnp.take(self.maxscore, rc).astype(jnp.int32)
        bound = jnp.where(ok_s, sc_s, jnp.where(derived, ms, _NEG_ONE))
        return jnp.where(valid, bound, _NEG_ONE)

    def pop_emissions(self, nodes, cursors):
        rc, stored = _pk_rank(self.c_ids, nodes,
                              _pk_iters(int(self.c_ids.shape[0])))
        e = jnp.clip(jnp.take(self.eptr, rc) + cursors, 0, self.e_size - 1)
        ms = jnp.take(self.maxscore, rc).astype(jnp.int32)
        node = jnp.where(stored, jnp.take(self.enode, e), nodes + 1)
        score = jnp.where(stored,
                          jnp.take(self.escore, e).astype(jnp.int32), ms)
        leaf = jnp.where(stored, jnp.take(self.eleaf, e) != 0, False)
        return node, score, leaf

    def lsid(self, nodes):
        size = max(int(self.l_ids.shape[0]), 1)
        rc, _ = _pk_rank(self.l_ids, nodes,
                         _pk_iters(int(self.l_ids.shape[0])))
        return jnp.take(self.l_sid,
                        jnp.clip(rc, 0, size - 1)).astype(jnp.int32)


def _search(tabs, loci,
            os_ref, oi_ref, oe_ref,
            gn_ref, gc_ref, gb_ref, ls_ref, li_ref, dm_ref, *,
            gens: int, expand: int, k: int, max_steps: int):
    """The generator-pool priority search, written once against the
    accessor seam; ``tabs`` is resident or streamed."""
    bq, f = loci.shape
    W, P = gens, expand

    # pool seeded with the locus antichain (reference: dynamic_update_slice
    # of loci into a -1-filled (W,) pool; the probe guarantees F <= W)
    gn = jnp.concatenate(
        [loci, jnp.full((bq, W - f), _NEG_ONE, jnp.int32)], axis=1) \
        if W > f else loci[:, :W]
    gc = jnp.zeros((bq, W), jnp.int32)
    gb = tabs.emit_bound(gn, gc)
    gn_ref[...] = jnp.where(gb >= 0, gn, _NEG_ONE)
    gc_ref[...] = gc
    gb_ref[...] = gb
    ls_ref[...] = jnp.full((bq, k), _NEG_ONE, jnp.int32)
    li_ref[...] = jnp.full((bq, k), _NEG_ONE, jnp.int32)
    dm_ref[:, 0] = jnp.full((bq,), _NEG_ONE, jnp.int32)

    iota_w = jax.lax.broadcasted_iota(jnp.int32, (bq, W), 1)

    def step(_, carry):
        gn, gc, gb = gn_ref[...], gc_ref[...], gb_ref[...]
        ls, li, dm = ls_ref[...], li_ref[...], dm_ref[:, 0]
        best = jnp.max(gb, axis=1)
        kth = ls[:, k - 1]
        # the reference while_loop's continuation condition, per query
        active = (best >= 0) & (kth < best)

        # pop the best P emissions across all generators
        topb, topi, _, _ = _topk_sorted(gb, P, ())
        sel_valid = topb >= 0
        sel_n = jnp.where(sel_valid, row_take(gn, topi), 0)
        em_node, em_score, em_leaf = tabs.pop_emissions(
            sel_n, row_take(gc, topi))

        # leaves -> result heap (k-round merge of heap + new leaves; heap
        # entries sit at lower indices, so ties keep the incumbent)
        leaf_ok = sel_valid & em_leaf
        new_ls = jnp.where(leaf_ok, em_score, _NEG_ONE)
        new_li = jnp.where(
            leaf_ok, tabs.lsid(jnp.where(leaf_ok, em_node, 0)), _NEG_ONE)
        ls2, _, (li2,), _ = _topk_sorted(
            jnp.concatenate([ls, new_ls], axis=1), k,
            (jnp.concatenate([li, new_li], axis=1),))

        # internal emissions -> new generators
        int_ok = sel_valid & ~em_leaf
        new_n = jnp.where(int_ok, em_node, _NEG_ONE)
        new_c = jnp.zeros((bq, P), jnp.int32)
        new_b = tabs.emit_bound(new_n, new_c)
        new_n = jnp.where(new_b >= 0, new_n, _NEG_ONE)

        # advance popped generators (one-hot scatter: topi rows are
        # distinct positions, so the sum is the reference's .at[].add)
        hit = (topi[:, :, None] == iota_w[:, None, :]) \
            & sel_valid[:, :, None]
        gc2 = gc + hit.sum(axis=1).astype(jnp.int32)
        gb2 = tabs.emit_bound(gn, gc2)
        gn2 = jnp.where(gb2 >= 0, gn, _NEG_ONE)

        # merge pools, keep top-W by bound; the sorted residue holds the
        # dropped bounds for the exactness tracker
        pool_n = jnp.concatenate([gn2, new_n], axis=1)
        pool_c = jnp.concatenate([gc2, new_c], axis=1)
        pool_b = jnp.concatenate([gb2, new_b], axis=1)
        keep_b, _, (keep_n, keep_c), residue = _topk_sorted(
            pool_b, W, (pool_n, pool_c))
        drop_best = jnp.max(jnp.maximum(residue, _NEG_ONE), axis=1)
        dm2 = jnp.maximum(dm, drop_best)

        m = active[:, None]
        gn_ref[...] = jnp.where(m, keep_n, gn)
        gc_ref[...] = jnp.where(m, keep_c, gc)
        gb_ref[...] = jnp.where(m, keep_b, gb)
        ls_ref[...] = jnp.where(m, ls2, ls)
        li_ref[...] = jnp.where(m, li2, li)
        dm_ref[:, 0] = jnp.where(active, dm2, dm)
        return carry

    jax.lax.fori_loop(0, max_steps, step, 0)

    gb, ls, dm = gb_ref[...], ls_ref[...], dm_ref[:, 0]
    best = jnp.max(gb, axis=1)
    kth = ls[:, k - 1]
    finished = ~((best >= 0) & (kth < best))
    # strict admissible bound: only a dropped candidate strictly above the
    # k-th score threatens exactness — an equal-bound drop ties at best
    # and must NOT trigger the doubled-width retry
    exact = (dm <= kth) & finished
    os_ref[...] = ls
    oi_ref[...] = li_ref[...]
    oe_ref[:, 0] = exact.astype(jnp.int32)


def _kernel(eptr_ref, enode_ref, escore_ref, eleaf_ref, lsid_ref,
            loci_ref,
            os_ref, oi_ref, oe_ref,
            gn_ref, gc_ref, gb_ref, ls_ref, li_ref, dm_ref, **statics):
    tabs = _ResidentEmit(eptr_ref[...], enode_ref[...], escore_ref[...],
                         eleaf_ref[...], lsid_ref[...])
    _search(tabs, loci_ref[...], os_ref, oi_ref, oe_ref,
            gn_ref, gc_ref, gb_ref, ls_ref, li_ref, dm_ref, **statics)


def _kernel_packed(flg_ref, c_ids_ref, eptr_ref, enode_ref, escore_ref,
                   eleaf_ref, ms_ref, l_ids_ref, lsid_ref,
                   loci_ref,
                   os_ref, oi_ref, oe_ref,
                   gn_ref, gc_ref, gb_ref, ls_ref, li_ref, dm_ref,
                   **statics):
    tabs = _PackedEmit(flg_ref[...], c_ids_ref[...], eptr_ref[...],
                       enode_ref[...], escore_ref[...], eleaf_ref[...],
                       ms_ref[...], l_ids_ref[...], lsid_ref[...])
    _search(tabs, loci_ref[...], os_ref, oi_ref, oe_ref,
            gn_ref, gc_ref, gb_ref, ls_ref, li_ref, dm_ref, **statics)


def _kernel_streamed(eptr_hbm, enode_hbm, escore_hbm, eleaf_hbm, lsid_hbm,
                     loci_ref,
                     os_ref, oi_ref, oe_ref,
                     gn_ref, gc_ref, gb_ref, ls_ref, li_ref, dm_ref,
                     pair_buf, row_buf, word_buf, sem_p, sem_r, sem_w, *,
                     emit_tile: int, **statics):
    tabs = _StreamedEmit(
        StreamTable(eptr_hbm, pair_buf, sem_p, 2),
        StreamTable(enode_hbm, row_buf, sem_r, emit_tile),
        StreamTable(escore_hbm, row_buf, sem_r, emit_tile),
        StreamTable(eleaf_hbm, row_buf, sem_r, emit_tile),
        StreamTable(lsid_hbm, word_buf, sem_w, 1),
        emit_tile)
    _search(tabs, loci_ref[...], os_ref, oi_ref, oe_ref,
            gn_ref, gc_ref, gb_ref, ls_ref, li_ref, dm_ref, **statics)


def _call(kernel, tables, table_specs, loci, scratch, *, k: int,
          gens: int, block_b: int, interpret: bool):
    # the exact flag and the dropped-max tracker ride as [B, 1] columns:
    # Mosaic tiles a rank-1 block only in multiples of 128 rows, a
    # [block_b, 1] column whenever block_b is a multiple of 8 (or the
    # whole batch)
    bsz, f = loci.shape
    s, i, e = pl.pallas_call(
        kernel,
        grid=(bsz // block_b,),
        in_specs=table_specs + [
            pl.BlockSpec((block_b, f), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, k), jnp.int32),
            jax.ShapeDtypeStruct((bsz, k), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, gens), jnp.int32),   # gn: generator nodes
            pltpu.VMEM((block_b, gens), jnp.int32),   # gc: emission cursors
            pltpu.VMEM((block_b, gens), jnp.int32),   # gb: admissible bounds
            pltpu.VMEM((block_b, k), jnp.int32),      # ls: result scores
            pltpu.VMEM((block_b, k), jnp.int32),      # li: result sids
            pltpu.VMEM((block_b, 1), jnp.int32),      # dropped_max tracker
        ] + scratch,
        interpret=interpret,
    )(*tables, loci)
    return s, i, e[:, 0]


@functools.partial(jax.jit, static_argnames=(
    "gens", "expand", "k", "max_steps", "block_b", "interpret"))
def beam_topk_batch(emit_ptr, emit_node, emit_score, emit_is_leaf, leaf_sid,
                    loci, *, gens: int, expand: int, k: int, max_steps: int,
                    block_b: int = 8, interpret: bool):
    """Fused beam phase 2 over a locus batch (VMEM-resident tables).

    loci int32[B, F] (-1 padded locus antichains, B divisible by block_b;
    the wrapper in ops.py pads — all-(-1) rows yield -1 results with
    exact=1).  Tables are the DeviceTrie emission arrays (``emit_is_leaf``
    as int32) and ``leaf_sid``; ``emit_node`` must be non-empty (the
    degenerate empty dictionary short-circuits in ops.py, mirroring the
    reference).  Returns (scores[B, k], sids[B, k], exact[B] int32 0/1) —
    bit-identical to ``jax.vmap(engine.beam.beam_topk)`` on the jnp
    substrate.
    """
    def full(a):
        shape = tuple(int(s) for s in a.shape)
        return pl.BlockSpec(shape, (lambda i: (0,) * len(shape)))

    kernel = functools.partial(_kernel, gens=gens, expand=expand, k=k,
                               max_steps=max_steps)
    tables = [emit_ptr, emit_node, emit_score, emit_is_leaf, leaf_sid]
    return _call(kernel, tables, [full(a) for a in tables], loci, [],
                 k=k, gens=gens, block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "gens", "expand", "k", "max_steps", "block_b", "interpret"))
def beam_topk_batch_packed(p_flags, c_ids, c_eptr, c_enode, c_escore,
                           c_eleaf, c_maxscore, l_ids, l_sid, loci, *,
                           gens: int, expand: int, k: int, max_steps: int,
                           block_b: int = 8, interpret: bool):
    """Compressed-layout variant of :func:`beam_topk_batch`: same
    contract and bit-identical results, reading the packed emission store
    (u8 flags, sorted ``c_ids`` side tables, u16-or-i32 scores/sids)
    VMEM-resident.  ``c_enode`` must be non-empty (the degenerate empty
    dictionary short-circuits in ops.py, like the uncompressed path)."""
    def full(a):
        shape = tuple(int(s) for s in a.shape)
        return pl.BlockSpec(shape, (lambda i: (0,) * len(shape)))

    kernel = functools.partial(_kernel_packed, gens=gens, expand=expand,
                               k=k, max_steps=max_steps)
    tables = [p_flags, c_ids, c_eptr, c_enode, c_escore, c_eleaf,
              c_maxscore, l_ids, l_sid]
    return _call(kernel, tables, [full(a) for a in tables], loci, [],
                 k=k, gens=gens, block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "gens", "expand", "k", "max_steps", "emit_tile", "block_b", "interpret"))
def beam_topk_batch_streamed(emit_ptr, emit_node, emit_score, emit_is_leaf,
                             leaf_sid, loci, *, gens: int, expand: int,
                             k: int, max_steps: int, emit_tile: int,
                             block_b: int = 8, interpret: bool):
    """HBM-resident variant of :func:`beam_topk_batch`: same contract,
    same results, but the emission tables stay in HBM and every step's
    pointer pairs / emission-row windows / sid gathers are
    double-buffered windowed DMAs.  ``emit_tile`` is the static window
    width from the tile-aligned layout (``EngineConfig.emit_tile``)."""
    hbm = pl.BlockSpec(memory_space=pltpu.ANY)
    kernel = functools.partial(_kernel_streamed, gens=gens, expand=expand,
                               k=k, max_steps=max_steps,
                               emit_tile=emit_tile)
    tables = [emit_ptr, emit_node, emit_score, emit_is_leaf, leaf_sid]
    lanes = block_b * gens
    scratch = [
        pltpu.VMEM((lanes, 2), jnp.int32),            # pointer-pair stage
        pltpu.VMEM((lanes, emit_tile), jnp.int32),    # emission-row windows
        pltpu.VMEM((lanes, 1), jnp.int32),            # sid gathers
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    return _call(kernel, tables, [hbm] * 5, loci, scratch,
                 k=k, gens=gens, block_b=block_b, interpret=interpret)
