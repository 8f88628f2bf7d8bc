"""Jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, ragged->dense conversion, interpret-mode
selection (interpret=True on CPU so the kernel bodies execute in Python;
compiled lowering on TPU), and fall-through to the pure-jnp references when
that is the right call (e.g. degenerate shapes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.candidate_topk import candidate_topk as _candidate_topk
from repro.kernels.embedding_bag import embedding_bag_dense as _embedding_bag
from repro.kernels.locus_merge import locus_topk_merge as _locus_topk_merge
from repro.kernels.topk_select import topk_select as _topk_select
from repro.kernels.trie_walk import trie_walk as _trie_walk


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(x, mult, fill):
    """Pad axis 0 of ``x`` up to a multiple of ``mult`` with ``fill``.

    Returns (padded, original_row_count).  Callers slice the kernel output
    back to the original count; the fill value must make padded rows
    inert for the kernel at hand (see ``_pad_query_batch``).
    """
    b = x.shape[0]
    pad = (-b) % mult
    if pad == 0:
        return x, b
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill), b


def _pad_query_batch(queries, qlens, mult):
    """Pad a (queries, qlens) batch together to a multiple of ``mult`` rows.

    Invariant: a padded row must walk to the root with depth 0 so it can
    be sliced off without a trace.  Two independent guards enforce it —
    chars fill with -1 (never matches an edge) AND qlens fill with 0 (the
    walk is inactive from step 0) — so a future change to either fill
    value alone stays safe.  Checked here on concrete (non-traced) calls.
    """
    q, b = _pad_rows(queries, mult, -1)
    ql, b2 = _pad_rows(qlens, mult, 0)
    assert b == b2, "queries and qlens disagree on batch size"
    if b < q.shape[0] and not isinstance(q, jax.core.Tracer):
        assert (np.asarray(q[b:]) < 0).all() and \
            (np.asarray(ql[b:]) == 0).all(), \
            "padded query rows must walk to the root with depth 0"
    return q, ql, b


def trie_walk(first_child, edge_char, edge_child, queries, qlens,
              block_q: int = 128, streamed: bool = False,
              walk_tile: int | None = None):
    """Batched longest-prefix walk; see kernels/trie_walk.py.

    ``streamed=True`` runs the HBM-resident DMA-streamed variant (same
    results; it uses a smaller default block — each query row streams
    its own windows) and then requires ``walk_tile``, the tile-aligned
    layout's static window width (``EngineConfig.walk_tile``) — a
    narrower window would silently truncate long CSR rows, so there is
    no default.
    """
    if streamed:
        if walk_tile is None:
            raise ValueError(
                "streamed trie_walk requires walk_tile (the layout's "
                "static window width, EngineConfig.walk_tile)")
        block_q = min(8, block_q)
    block_q = min(block_q, max(int(queries.shape[0]), 1))
    q, ql, b = _pad_query_batch(queries, qlens, block_q)
    if streamed and int(edge_char.shape[0]) > 0:
        from repro.kernels.trie_walk import trie_walk_streamed
        node, depth = trie_walk_streamed(
            first_child, edge_char, edge_child, q, ql, tile=walk_tile,
            block_q=block_q, interpret=_interpret())
    else:
        node, depth = _trie_walk(first_child, edge_char, edge_child, q, ql,
                                 block_q=block_q, interpret=_interpret())
    return node[:b], depth[:b]


def _nonempty(a, fill=-1):
    """Pad a 0-row table to one inert row (pallas refs need size >= 1;
    callers gate usage with the matching ``has_*`` static)."""
    if int(a.shape[0]) > 0:
        return a
    return jnp.full((1,) + tuple(a.shape[1:]), fill, a.dtype)


def _pair_ptr(ptr):
    """Pad a CSR pointer table to >= 2 entries so ``ptr[rc + 1]`` stays
    in bounds for the packed kernels' padded single ``-1`` id row (whose
    lookups are discarded — no node id matches -1)."""
    if int(ptr.shape[0]) >= 2:
        return ptr
    return jnp.zeros((2,), ptr.dtype)


def _is_packed(t) -> bool:
    """Compressed-layout probe on a duck-typed DeviceTrie (mirrors
    ``engine.packed.is_packed`` without importing the engine)."""
    return getattr(t, "p_labels", None) is not None \
        and int(t.p_labels.shape[0]) > 0


def locus_walk(t, cfg, queries, qlens, block_q: int = 8,
               streamed: bool = False):
    """Fused synonym-aware locus DP; see kernels/locus_dp.py.

    t: engine DeviceTrie (duck-typed — only the array fields are read);
    cfg: EngineConfig.  queries int32[B, L] (-1 padded), qlens int32[B].
    Returns (loci[B, F], overflow[B]) matching the jnp reference DP
    bit-for-bit.  ``streamed=True`` keeps the dictionary-sized tables in
    HBM and streams windows per access (same results).
    """
    from repro.kernels.locus_dp import locus_dp_walk as _locus_dp
    from repro.kernels.locus_dp import \
        locus_dp_walk_streamed as _locus_dp_streamed

    block_q = min(block_q, max(int(queries.shape[0]), 1))
    q, ql, b = _pad_query_batch(queries, qlens, block_q)
    if _is_packed(t):
        from repro.kernels.locus_dp import (
            locus_dp_walk_packed as _locus_dp_packed,
            locus_dp_walk_packed_streamed as _locus_dp_packed_streamed)

        tables = (
            t.p_labels, t.p_flags, t.c_ids, t.c_tout,
            _nonempty(t.b_ids), _pair_ptr(t.b_ptr),
            _nonempty(t.b_char), _nonempty(t.b_child),
            _nonempty(t.sb_ids), _pair_ptr(t.sb_ptr),
            _nonempty(t.sb_char), _nonempty(t.sb_child),
            _nonempty(t.t_ids), _nonempty(t.t_plane),
            _nonempty(t.la_ids), _pair_ptr(t.la_ptr),
            _nonempty(t.link_rule), _nonempty(t.link_target),
            t.r_first_child, _nonempty(t.r_edge_char),
            _nonempty(t.r_edge_child), t.r_term_plane)
        statics = dict(
            frontier=cfg.frontier, rule_matches=cfg.rule_matches,
            max_lhs_len=cfg.max_lhs_len, max_terms=cfg.max_terms_per_node,
            # syn nodes exist iff teleports do (every expanded branch
            # ends in one) or a non-unary syn row was stored
            has_syn=int(t.t_ids.shape[0]) > 0
            or int(t.sb_child.shape[0]) > 0,
            has_tele=cfg.teleports > 0,
            has_links=int(t.link_rule.shape[0]) > 0,
            edit_budget=cfg.edit_budget, branch_width=cfg.branch_width,
            block_q=block_q, interpret=_interpret())
        fn = _locus_dp_packed_streamed if streamed else _locus_dp_packed
        loci, overflow = fn(*tables, q, ql, **statics)
        return loci[:b], overflow[:b]
    tables = (
        t.first_child, t.edge_char, t.edge_child,
        t.s_first_child, _nonempty(t.s_edge_char), _nonempty(t.s_edge_child),
        t.syn_mask.astype(jnp.int32), t.tout, t.tele_plane,
        t.link_ptr, _nonempty(t.link_rule), _nonempty(t.link_target),
        t.r_first_child, _nonempty(t.r_edge_char), _nonempty(t.r_edge_child),
        t.r_term_plane)
    statics = dict(
        frontier=cfg.frontier, rule_matches=cfg.rule_matches,
        max_lhs_len=cfg.max_lhs_len, max_terms=cfg.max_terms_per_node,
        has_syn=int(t.s_edge_char.shape[0]) > 0,
        has_tele=cfg.teleports > 0,
        has_links=int(t.link_rule.shape[0]) > 0,
        edit_budget=cfg.edit_budget, branch_width=cfg.branch_width,
        block_q=block_q, interpret=_interpret())
    if streamed:
        loci, overflow = _locus_dp_streamed(
            *tables, q, ql, walk_tile=cfg.walk_tile,
            link_tile=cfg.link_tile, **statics)
    else:
        loci, overflow = _locus_dp(*tables, q, ql, **statics)
    return loci[:b], overflow[:b]


def beam_topk(t, cfg, loci, k: int, block_b: int = 8,
              streamed: bool = False):
    """Fused beam phase 2; see kernels/beam_topk.py.

    t: engine DeviceTrie (duck-typed — only the emission arrays and
    ``leaf_sid`` are read); cfg: EngineConfig (``gens``/``expand``/
    ``max_steps`` become the kernel's static trip counts).
    loci int32[B, F] (-1 padded locus antichains).
    Returns (scores[B, k], sids[B, k], exact[B] bool) matching
    ``jax.vmap(engine.beam.beam_topk)`` bit-for-bit.  ``streamed=True``
    keeps the emission tables in HBM and streams row windows per step
    (same results).
    """
    from repro.kernels.beam_topk import beam_topk_batch as _beam_topk
    from repro.kernels.beam_topk import \
        beam_topk_batch_streamed as _beam_topk_streamed

    B = int(loci.shape[0])
    packed = _is_packed(t)
    empty = (int(t.c_enode.shape[0]) == 0 if packed
             else int(t.emit_node.shape[0]) == 0)
    if empty:
        # degenerate empty dictionary: mirror the reference's short-circuit
        return (jnp.full((B, k), -1, jnp.int32),
                jnp.full((B, k), -1, jnp.int32),
                jnp.ones((B,), bool))
    if packed:
        if streamed:
            raise ValueError(
                "no streamed packed beam tier — the substrate probe "
                "routes over-budget packed tries to the jnp reference")
        from repro.kernels.beam_topk import \
            beam_topk_batch_packed as _beam_topk_packed

        block_b = min(block_b, max(B, 1))
        l, b = _pad_rows(loci, block_b, -1)
        s, i, e = _beam_topk_packed(
            t.p_flags, t.c_ids, t.c_eptr, t.c_enode, t.c_escore,
            t.c_eleaf, t.c_maxscore, _nonempty(t.l_ids),
            _nonempty(t.l_sid), l, gens=cfg.gens, expand=cfg.expand,
            k=k, max_steps=cfg.max_steps, block_b=block_b,
            interpret=_interpret())
        return s[:b], i[:b], e[:b].astype(bool)
    block_b = min(block_b, max(B, 1))
    # padded rows are all -1 loci => dead pool, -1 results, exact; sliced off
    l, b = _pad_rows(loci, block_b, -1)
    tables = (t.emit_ptr, t.emit_node, t.emit_score,
              t.emit_is_leaf.astype(jnp.int32), t.leaf_sid)
    if streamed:
        s, i, e = _beam_topk_streamed(
            *tables, l, gens=cfg.gens, expand=cfg.expand, k=k,
            max_steps=cfg.max_steps, emit_tile=cfg.emit_tile,
            block_b=block_b, interpret=_interpret())
    else:
        s, i, e = _beam_topk(
            *tables, l, gens=cfg.gens, expand=cfg.expand, k=k,
            max_steps=cfg.max_steps, block_b=block_b,
            interpret=_interpret())
    return s[:b], i[:b], e[:b].astype(bool)


def topk_select(scores, payload, k: int, block_b: int = 8):
    """Fused top-k with payload; see kernels/topk_select.py."""
    if k >= scores.shape[1]:
        return ref.topk_select_ref(scores, payload, k)
    block_b = min(block_b, max(int(scores.shape[0]), 1))
    s, b = _pad_rows(scores, block_b, -(2**31 - 1))
    p, _ = _pad_rows(payload, block_b, -1)
    ts, tp = _topk_select(s, p, k, block_b=block_b, interpret=_interpret())
    return ts[:b], tp[:b]


def cached_topk_merge(loci, topk_score, topk_sid, k: int, block_b: int = 8):
    """Fused cached-top-K locus gather + merge; see kernels/locus_merge.py.

    loci int32[B, F] (-1 padded); topk_score/topk_sid int32[N, K].
    Returns (scores[B, k], sids[B, k]).
    """
    f = int(loci.shape[1])
    kk = int(topk_score.shape[1])
    if k >= f * kk:
        # selection degenerates to sorting the whole (tiny) union
        s, p = ref.cached_topk_merge_ref(loci, topk_score, topk_sid,
                                         min(k, f * kk))
        pad = ((0, 0), (0, k - s.shape[1]))
        return jnp.pad(s, pad, constant_values=-1), \
            jnp.pad(p, pad, constant_values=-1)
    block_b = min(block_b, max(int(loci.shape[0]), 1))
    # padded rows are all -1 loci => every candidate masked empty; sliced off
    l, b = _pad_rows(loci, block_b, -1)
    s, p = _locus_topk_merge(l, topk_score, topk_sid, k, block_b=block_b,
                             interpret=_interpret())
    return s[:b], p[:b]


def cached_topk_merge_packed(t, loci, k: int, block_b: int = 8):
    """Cached merge over the compressed layout's quantized cache.

    Translates each locus to its chain-representative rank in ``c_ids``
    (an unstored unary node's cache row equals its representative's, a
    pack-time invariant) and decodes the u16-or-i32 row planes back to
    raw i32 in-jit, then reuses :func:`cached_topk_merge` unchanged —
    the candidates and their order are exactly the uncompressed path's.
    """
    from repro.core.engine import packed as pk

    valid = loci >= 0
    rc, _ = pk._rank(t.c_ids, jnp.where(valid, loci, 0))
    rloci = jnp.where(valid, rc, -1)
    dec_s = pk.decode_cache_scores(t.pc_score, t.pc_base)
    dec_i = pk.decode_cache_sids(t.pc_sid)
    return cached_topk_merge(rloci, dec_s, dec_i, k, block_b=block_b)


def embedding_bag(table, indices, offsets, weights=None, mode: str = "sum",
                  max_bag: int | None = None, block_b: int = 128):
    """EmbeddingBag over a ragged (indices, offsets) batch.

    indices int32[I] (-1 entries skipped), offsets int32[B+1].
    Densifies to [B, max_bag] then runs the Pallas kernel.
    """
    idx = np.asarray(indices)
    off = np.asarray(offsets)
    bsz = len(off) - 1
    lens = np.diff(off)
    mb = int(max_bag if max_bag is not None else max(int(lens.max(initial=1)), 1))
    dense = np.full((bsz, mb), -1, np.int32)
    wdense = np.zeros((bsz, mb), np.asarray(table).dtype)
    w = np.asarray(weights) if weights is not None else np.ones(len(idx), np.asarray(table).dtype)
    for i in range(bsz):
        n = min(int(lens[i]), mb)
        dense[i, :n] = idx[off[i] : off[i] + n]
        wdense[i, :n] = w[off[i] : off[i] + n]
    return embedding_bag_dense(table, jnp.asarray(dense), jnp.asarray(wdense),
                               mode=mode, block_b=block_b)


def embedding_bag_dense(table, idx, weights, mode: str = "sum",
                        block_b: int = 128):
    """EmbeddingBag on an already-dense [B, MB] index matrix."""
    block_b = min(block_b, max(int(idx.shape[0]), 1))
    idx_p, b = _pad_rows(idx, block_b, -1)
    w_p, _ = _pad_rows(weights, block_b, 0)
    out = _embedding_bag(table, idx_p, w_p, mode=mode, block_b=block_b,
                         interpret=_interpret())
    return out[:b]


def candidate_topk(query, candidates, k: int, block_c: int = 1024):
    """Fused dot scoring + running top-k; see kernels/candidate_topk.py."""
    block_c = min(block_c, max(int(candidates.shape[0]), 1))
    c, n = _pad_rows(candidates, block_c, 0)
    if n < c.shape[0]:
        # padded rows score 0; shift scores by masking is handled by id cut
        pass
    s, i = _candidate_topk(query, c, k, block_c=block_c,
                           interpret=_interpret())
    # drop any padded-row winners (can only appear when k ~ C)
    bad = i >= n
    s = jnp.where(bad, jnp.float32(-3.0e38), s)
    i = jnp.where(bad, -1, i)
    return s, i
