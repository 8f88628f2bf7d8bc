"""Pallas TPU kernel: fused synonym-aware locus DP (phase 1, tt/et/ht).

The paper's core walk — reach[pos] = trie nodes reachable by consuming
p[:pos] under some rewriting — fused into one kernel per query block.
The pure-jnp path (`engine/locus.py`) runs the same sweep as a vmap of a
per-query `fori_loop` whose every inner step (CSR child lookup, teleport
gather, link-store search, dedup-compaction) is a separate XLA op; this
kernel carries the whole (L+1, F) frontier buffer on-chip through one
block-wide position loop and executes the sweep as masked fixed-trip
loops over the packed rule plane (`trie_build.pack_rule_planes`):

- literal char step: binary-searched CSR child lookup over the dict and
  synonym-branch edge sets;
- teleports (ET/HT): one vectorized gather from the dense, -1-padded
  ``tele_plane``;
- rule steps (TT/HT): the rule-trie descent is inlined per position, so
  every full-lhs match lands at a *static* end offset and the link-store
  step (one ``link_ptr`` load + one binary search over ``link_rule``)
  merges straight into the matching frontier row;
- dedup-compaction: one sort + rank-scatter per merge, bit-identical to
  ``primitives.dedup_pad``;
- finalization: synonym-loci drop + dedup + preorder-interval antichain
  reduction, all in-block.

Every trip count (L, max_lhs_len, terms/node, frontier width, binary
search rounds) is static, so there is no data-dependent control flow —
the VPU executes the whole sweep without divergence.  Results (loci and
overflow counts) are bit-identical to the jnp reference engine; the
substrate parity suite enforces this in interpret mode on CPU.

The sweep body is written once against a small table-accessor seam and
runs in two tiers:

- *resident* (``locus_dp_walk``): every table and the rule plane live
  whole in VMEM, like the trie-walk kernel's CSRs;
- *streamed* (``locus_dp_walk_streamed``): the dictionary-sized tables
  (dict/synonym CSRs, ``syn_mask``/``tout``, teleport-plane rows and the
  link store) stay in HBM and each access double-buffers pointer pairs /
  row windows / plane rows into VMEM scratch via ``make_async_copy``
  (:mod:`repro.kernels.stream`); only the rule trie — sized by the rule
  set, thousands of entries, not the dictionary — stays VMEM-resident.
  The tile-aligned layout (``trie_build.pack_stream_tiles``) guarantees
  one window covers any CSR row, so the in-window searches probe exactly
  what the resident forms probe: both tiers are bit-identical to the
  reference DP.

`PallasSubstrate.can_walk_batch` probes the static shape envelope and
picks the tier by comparing table bytes against the VMEM budget; shapes
outside the envelope fall back to the jnp DP.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stream import (StreamTable, row_take, stream_csr_children,
                                  window_lower_bound)

# plain python ints: jnp scalars would be captured as constants by the
# pallas kernel tracer
_INT_MAX = 2**31 - 1
_NEG_ONE = -1


def _iters(n: int) -> int:
    """Binary-search trip count for an n-row table (matches
    ``primitives.iters_for``)."""
    return max(1, int(math.ceil(math.log2(max(n, 1) + 1))))


def _lower_bound(arr, lo, hi, x, iters: int):
    """First index in [lo, hi) with arr[idx] >= x (fixed trips)."""
    size = max(int(arr.shape[0]), 1)
    for _ in range(iters):
        cont = lo < hi
        mid = (lo + hi) >> 1
        v = jnp.take(arr, jnp.clip(mid, 0, size - 1))
        go_right = v < x
        lo = jnp.where(cont & go_right, mid + 1, lo)
        hi = jnp.where(cont & ~go_right, mid, hi)
    return lo


def _csr_children(ptr, chars, children, nodes, ch):
    """children[nodes] labelled ch; -1 propagated/absent.  nodes and ch
    broadcast together (same semantics as ``primitives.csr_child_lookup``)."""
    valid = nodes >= 0
    nn = jnp.where(valid, nodes, 0)
    lo = jnp.take(ptr, nn)
    hi = jnp.take(ptr, nn + 1)
    pos = _lower_bound(chars, lo, hi, ch, _iters(int(chars.shape[0])))
    size = max(int(chars.shape[0]), 1)
    posc = jnp.clip(pos, 0, size - 1)
    found = (pos < hi) & (jnp.take(chars, posc) == ch) & valid & (ch >= 0)
    return jnp.where(found, jnp.take(children, posc), _NEG_ONE)


def _enc(nodes, d, E: int):
    """Pack (node, edits-used d) into one frontier state: node*(E+1)+d.
    Identity at E=0 (exact-mode traces untouched); -1 stays -1.  Mirrors
    ``engine.locus.encode_states``."""
    if E == 0:
        return nodes
    return jnp.where(nodes < 0, _NEG_ONE, nodes * (E + 1) + d)


def _dec(states, E: int):
    """Inverse of :func:`_enc`: (nodes, d); -1 -> (-1, 0)."""
    if E == 0:
        return states, jnp.zeros_like(states)
    nodes = jnp.where(states < 0, _NEG_ONE, states // (E + 1))
    d = jnp.where(states < 0, 0, states % (E + 1))
    return nodes, d


def _dedup(cand, width: int):
    """Row-wise unique-compact of cand [BQ, V] to [BQ, width] ascending,
    -1 padded; returns (out, n_dropped[BQ]).  Bit-identical to
    ``primitives.dedup_pad`` per row (same sort + rank-scatter)."""
    bq, v = cand.shape
    big = jnp.where(cand < 0, _INT_MAX, cand)
    s = jnp.sort(big, axis=1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (bq, v), 1)
    keep = (idx == 0) | (s != jnp.roll(s, 1, axis=1))
    keep &= s != _INT_MAX
    rank = jnp.cumsum(keep, axis=1) - 1          # position among kept
    n_uniq = (rank[:, -1] + 1).astype(jnp.int32)
    dst = jnp.where(keep & (rank < width), rank, width)  # width = drop slot
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, v), 0)
    out = jnp.full((bq, width + 1), _NEG_ONE, jnp.int32)
    out = out.at[rows, dst].set(s, mode="drop")
    out = jnp.where(out == _INT_MAX, _NEG_ONE, out)[:, :width]
    return out, jnp.maximum(n_uniq - width, 0).astype(jnp.int32)


def _plane_rows(plane, nodes):
    """Gather full plane rows for a node vector: plane [N, W], nodes
    [BQ] or [BQ, F] -> [..., W] (rows of invalid nodes read row 0 and are
    masked by the caller)."""
    w = int(plane.shape[1])
    offs = jnp.arange(w, dtype=jnp.int32)
    idx = nodes[..., None] * w + offs
    return jnp.take(plane.reshape(-1), idx)


def _link_lookup(link_ptr, link_rule, link_target, anchors, rid):
    """(anchor, rule) -> target or -1.  anchors [BQ, F], rid [BQ]."""
    n_link = int(link_rule.shape[0])
    valid = anchors >= 0
    a = jnp.where(valid, anchors, 0)
    lo = jnp.take(link_ptr, a)
    hi = jnp.take(link_ptr, a + 1)
    pos = _lower_bound(link_rule, lo, hi, rid[:, None], _iters(n_link))
    posc = jnp.clip(pos, 0, max(n_link, 1) - 1)
    found = (pos < hi) & (jnp.take(link_rule, posc) == rid[:, None]) & valid
    return jnp.where(found, jnp.take(link_target, posc), _NEG_ONE)


# ---------------------------------------------------------------------------
# table-accessor seam: the sweep body is tier-agnostic
# ---------------------------------------------------------------------------


class _ResidentTables:
    """VMEM-resident table reads (the original fused kernel's forms)."""

    def __init__(self, fc, ec, echild, sfc, sec, sechild, syn_mask, tout,
                 tele_plane, lptr, lrule, ltgt):
        self.fc, self.ec, self.echild = fc, ec, echild
        self.sfc, self.sec, self.sechild = sfc, sec, sechild
        self.syn_mask, self.tout_arr = syn_mask, tout
        self.tele_plane = tele_plane
        self.lptr, self.lrule, self.ltgt = lptr, lrule, ltgt

    def dict_children(self, nodes, ch):
        return _csr_children(self.fc, self.ec, self.echild, nodes, ch)

    def syn_children(self, nodes, ch):
        return _csr_children(self.sfc, self.sec, self.sechild, nodes, ch)

    def dict_child_window(self, nodes, width: int):
        """All dict children of each node: (chars, children) [..., width],
        -1 padded — the bounded-edit substitute/delete source.  The
        tile-aligned edge arrays are padded a whole tile past their real
        length and width <= walk_tile, so the window loads stay in
        bounds."""
        valid = nodes >= 0
        nn = jnp.where(valid, nodes, 0)
        lo = jnp.take(self.fc, nn)
        cnt = jnp.where(valid, jnp.take(self.fc, nn + 1) - lo, 0)
        js = jax.lax.broadcasted_iota(
            jnp.int32, tuple(nodes.shape) + (width,), nodes.ndim)
        size = max(int(self.ec.shape[0]), 1)
        idx = jnp.clip(lo[..., None] + js, 0, size - 1)
        m = js < cnt[..., None]
        chars = jnp.where(m, jnp.take(self.ec, idx), _NEG_ONE)
        children = jnp.where(m, jnp.take(self.echild, idx), _NEG_ONE)
        return chars, children

    def tele_rows(self, nodes):
        return _plane_rows(self.tele_plane, nodes)

    def syn_mask_of(self, nodes):
        return jnp.take(self.syn_mask, nodes)

    def tout_of(self, nodes):
        return jnp.take(self.tout_arr, nodes)

    def link_lookup(self, anchors, rid):
        return _link_lookup(self.lptr, self.lrule, self.ltgt, anchors, rid)


class _StreamedTables:
    """HBM-resident tables behind double-buffered windowed DMA.

    Every lookup streams the pointer pairs / row windows / plane rows it
    touches into the shared staging buffers and computes the same values
    the resident forms compute — the window always covers the whole row,
    so the in-window searches are bit-identical.
    """

    def __init__(self, fc_t, ec_t, ek_t, sfc_t, sec_t, sek_t, mask_t,
                 tout_t, tele_t, lptr_t, lrule_t, ltgt_t,
                 walk_iters: int, link_iters: int):
        self.fc_t, self.ec_t, self.ek_t = fc_t, ec_t, ek_t
        self.sfc_t, self.sec_t, self.sek_t = sfc_t, sec_t, sek_t
        self.mask_t, self.tout_t, self.tele_t = mask_t, tout_t, tele_t
        self.lptr_t, self.lrule_t, self.ltgt_t = lptr_t, lrule_t, ltgt_t
        self.walk_iters, self.link_iters = walk_iters, link_iters

    def dict_children(self, nodes, ch):
        return stream_csr_children(self.fc_t, self.ec_t, self.ek_t,
                                   nodes, ch, self.walk_iters)

    def syn_children(self, nodes, ch):
        return stream_csr_children(self.sfc_t, self.sec_t, self.sek_t,
                                   nodes, ch, self.walk_iters)

    def dict_child_window(self, nodes, width: int):
        """Streamed form of the resident window: the (lo, hi) pointer
        pairs and the ``[lo, lo + walk_tile)`` row windows ride the same
        staging buffers as the CSR child lookups; walk_tile >= the real
        fanout, so the returned (wider) window carries the same children,
        -1 beyond each row's count — content-identical to the resident
        window for every downstream merge."""
        del width   # the staged window is walk_tile wide; extras mask off
        valid = nodes >= 0
        nn = jnp.where(valid, nodes, 0)
        lo, hi = self.fc_t.pairs(nn)
        cnt = jnp.where(valid, hi - lo, 0)
        wc = self.ec_t.windows(lo)
        wk = self.ek_t.windows(lo)
        js = jax.lax.broadcasted_iota(jnp.int32, wc.shape, wc.ndim - 1)
        m = js < cnt[..., None]
        return jnp.where(m, wc, _NEG_ONE), jnp.where(m, wk, _NEG_ONE)

    def tele_rows(self, nodes):
        return self.tele_t.windows(nodes)

    def syn_mask_of(self, nodes):
        return self.mask_t.gather(nodes)

    def tout_of(self, nodes):
        return self.tout_t.gather(nodes)

    def link_lookup(self, anchors, rid):
        valid = anchors >= 0
        ridb = jnp.broadcast_to(rid[:, None], anchors.shape)
        a = jnp.where(valid, anchors, 0)
        lo, hi = self.lptr_t.pairs(a)
        span = hi - lo
        wr = self.lrule_t.windows(lo)
        w = int(wr.shape[-1])
        pos = window_lower_bound(wr, span, ridb, self.link_iters)
        posc = jnp.clip(pos, 0, w - 1)
        found = (pos < span) & \
            (row_take(wr, posc[..., None])[..., 0] == ridb) & valid
        tgt = row_take(self.ltgt_t.windows(lo), posc[..., None])[..., 0]
        return jnp.where(found, tgt, _NEG_ONE)


def _packed_rank(ids, nodes):
    """Position of each node in a sorted id table: (clipped_rank, exact).
    Mirrors ``engine.packed._rank``; a padded single ``-1`` row (empty
    table, see ops._nonempty) never matches a node id >= 0."""
    size = int(ids.shape[0])
    lo = jnp.zeros_like(nodes)
    hi = jnp.full_like(nodes, size)
    pos = _lower_bound(ids, lo, hi, nodes, _iters(size))
    rc = jnp.clip(pos, 0, max(size, 1) - 1)
    return rc, (pos < size) & (jnp.take(ids, rc) == nodes)


# p_flags bits (mirror engine.packed; plain ints for kernel tracing)
_PK_DICT_UNARY = 1
_PK_SYN_UNARY = 2
_PK_IS_SYN = 4


class _PackedResidentTables:
    """VMEM-resident reads of the compressed layout — the same forms as
    :mod:`repro.core.engine.packed`, lowered through the seam the sweep
    already speaks.  Narrow (u8) values widen to i32 at the read."""

    def __init__(self, labels, flags, c_ids, c_tout,
                 b_ids, b_ptr, b_char, b_child,
                 sb_ids, sb_ptr, sb_char, sb_child,
                 t_ids, tele_plane, la_ids, la_ptr, lrule, ltgt):
        self.labels, self.flags = labels, flags
        self.c_ids, self.c_tout = c_ids, c_tout
        self.b_ids, self.b_ptr = b_ids, b_ptr
        self.b_char, self.b_child = b_char, b_child
        self.sb_ids, self.sb_ptr = sb_ids, sb_ptr
        self.sb_char, self.sb_child = sb_char, sb_child
        self.t_ids, self.tele_plane = t_ids, tele_plane
        self.la_ids, self.la_ptr = la_ids, la_ptr
        self.lrule, self.ltgt = lrule, ltgt
        self.n_nodes = int(labels.shape[0])

    # the two N-sized plane reads — the only loads the streamed packed
    # tier overrides
    def _flags(self, nodes):
        return jnp.take(self.flags, nodes).astype(jnp.int32)

    def _label_next(self, nodes):
        return jnp.take(self.labels,
                        jnp.clip(nodes + 1, 0,
                                 self.n_nodes - 1)).astype(jnp.int32)

    def _children(self, ids, ptr, chars, children, unary_bit, nodes, ch):
        valid = nodes >= 0
        n = jnp.where(valid, nodes, 0)
        ok_u = ((self._flags(n) & unary_bit) != 0) \
            & (self._label_next(n) == ch) & valid & (ch >= 0)
        u_child = jnp.where(ok_u, n + 1, _NEG_ONE)
        rc, isrow = _packed_rank(ids, n)
        lo = jnp.take(ptr, rc)
        hi = jnp.where(isrow, jnp.take(ptr, rc + 1), lo)
        pos = _lower_bound(chars, lo, hi, ch, _iters(int(chars.shape[0])))
        posc = jnp.clip(pos, 0, max(int(chars.shape[0]), 1) - 1)
        found = (pos < hi) & \
            (jnp.take(chars, posc).astype(jnp.int32) == ch) \
            & valid & (ch >= 0)
        row_child = jnp.where(found, jnp.take(children, posc), _NEG_ONE)
        return jnp.where(isrow, row_child, u_child)

    def dict_children(self, nodes, ch):
        return self._children(self.b_ids, self.b_ptr, self.b_char,
                              self.b_child, _PK_DICT_UNARY, nodes, ch)

    def syn_children(self, nodes, ch):
        return self._children(self.sb_ids, self.sb_ptr, self.sb_char,
                              self.sb_child, _PK_SYN_UNARY, nodes, ch)

    def dict_child_window(self, nodes, width: int):
        """Packed form of the dict-child window (mirrors
        ``engine.packed.dict_child_window``): a unary node's window is its
        single (label, v+1) pair in column 0; branching nodes read their
        sparse ``b_*`` row.  Inherited by the streamed packed tier — only
        the flag/label plane reads differ there."""
        valid = nodes >= 0
        n = jnp.where(valid, nodes, 0)
        js = jax.lax.broadcasted_iota(
            jnp.int32, tuple(nodes.shape) + (width,), nodes.ndim)
        u_ok = (((self._flags(n) & _PK_DICT_UNARY) != 0) & valid)[..., None] \
            & (js == 0)
        chars = jnp.where(u_ok, self._label_next(n)[..., None], _NEG_ONE)
        children = jnp.where(u_ok, (n + 1)[..., None], _NEG_ONE)
        rc, isrow = _packed_rank(self.b_ids, n)
        lo = jnp.take(self.b_ptr, rc).astype(jnp.int32)
        cnt = jnp.where(isrow & valid,
                        jnp.take(self.b_ptr, rc + 1).astype(jnp.int32) - lo,
                        0)
        size = max(int(self.b_char.shape[0]), 1)
        idx = jnp.clip(lo[..., None] + js, 0, size - 1)
        m = js < cnt[..., None]
        chars = jnp.where(
            m, jnp.take(self.b_char, idx).astype(jnp.int32), chars)
        children = jnp.where(
            m, jnp.take(self.b_child, idx).astype(jnp.int32), children)
        return chars, children

    def tele_rows(self, nodes):
        rc, exact = _packed_rank(self.t_ids, nodes)
        rows = _plane_rows(self.tele_plane, rc)
        return jnp.where(exact[..., None], rows, _NEG_ONE)

    def syn_mask_of(self, nodes):
        # 0/IS_SYN int; the sweep only compares against 0
        return self._flags(nodes) & _PK_IS_SYN

    def tout_of(self, nodes):
        rc, _ = _packed_rank(self.c_ids, nodes)
        return jnp.where((self._flags(nodes) & _PK_IS_SYN) != 0,
                         nodes + 1, jnp.take(self.c_tout, rc))

    def link_lookup(self, anchors, rid):
        n_link = int(self.lrule.shape[0])
        valid = anchors >= 0
        a = jnp.where(valid, anchors, 0)
        rc, isrow = _packed_rank(self.la_ids, a)
        lo = jnp.take(self.la_ptr, rc)
        hi = jnp.where(isrow, jnp.take(self.la_ptr, rc + 1), lo)
        pos = _lower_bound(self.lrule, lo, hi, rid[:, None], _iters(n_link))
        posc = jnp.clip(pos, 0, max(n_link, 1) - 1)
        found = (pos < hi) & \
            (jnp.take(self.lrule, posc) == rid[:, None]) & valid
        return jnp.where(found, jnp.take(self.ltgt, posc), _NEG_ONE)


class _PackedStreamedTables(_PackedResidentTables):
    """Packed tier with the two N-sized u8 planes (labels/flags) DMA'd
    per access; every sparse side table — branch-count-sized, tiny next
    to the planes — stays VMEM-resident.  ``StreamTable.windows`` widens
    the u8 staging rows to i32, so the reads are the resident forms'."""

    def __init__(self, lbl_t, flg_t, *side):
        self.lbl_t, self.flg_t = lbl_t, flg_t
        (self.c_ids, self.c_tout,
         self.b_ids, self.b_ptr, self.b_char, self.b_child,
         self.sb_ids, self.sb_ptr, self.sb_char, self.sb_child,
         self.t_ids, self.tele_plane,
         self.la_ids, self.la_ptr, self.lrule, self.ltgt) = side
        self.n_nodes = int(lbl_t.hbm.shape[0])

    def _flags(self, nodes):
        return self.flg_t.gather(nodes)

    def _label_next(self, nodes):
        return self.lbl_t.gather(
            jnp.clip(nodes + 1, 0, self.n_nodes - 1))


def _tele_expand(tabs, row, width: int, E: int):
    """Frontier row [BQ, F] -> row plus teleport targets, dedup'd back.
    In bounded-edit mode targets inherit the source state's edit count."""
    bq, f = row.shape
    nodes, d = _dec(row, E)
    valid = nodes >= 0
    nn = jnp.where(valid, nodes, 0)
    tgt = jnp.where(valid[:, :, None], tabs.tele_rows(nn), _NEG_ONE)
    tgt = _enc(tgt, d[:, :, None], E)
    return _dedup(jnp.concatenate([row, tgt.reshape(bq, -1)], axis=1), width)


def _expand_frontier(tabs, row, width: int, E: int, BW: int,
                     has_tele: bool):
    """Teleport expansion + E-round delete closure — mirrors
    ``engine.locus.expand_frontier`` (teleports attach only to synonym
    nodes, deletes only descend dict children, so this order reaches the
    joint fixpoint)."""
    bq = row.shape[0]
    drop_total = jnp.zeros((bq,), jnp.int32)
    if has_tele:
        row, drop = _tele_expand(tabs, row, width, E)
        drop_total += drop
    for _ in range(E):
        nodes, d = _dec(row, E)
        _, children = tabs.dict_child_window(nodes, BW)
        ok = (children >= 0) & (d < E)[..., None]
        tgt = _enc(jnp.where(ok, children, _NEG_ONE), (d + 1)[..., None], E)
        row, drop = _dedup(
            jnp.concatenate([row, tgt.reshape(bq, -1)], axis=1), width)
        drop_total += drop
    return row, drop_total


def _sweep(tabs, rfc, rec, rechild, rterm, q, qlen,
           loci_ref, ov_ref, *,
           frontier: int, rule_matches: int, max_lhs_len: int,
           max_terms: int, has_syn: bool, has_tele: bool, has_links: bool,
           seq_len: int, edit_budget: int = 0, branch_width: int = 1):
    """The fused frontier sweep, written once against the accessor seam;
    ``tabs`` is resident or streamed, the rule trie (rfc/rec/rechild/
    rterm) is always VMEM-resident.

    The position loop is a ``fori_loop`` with the (BQ, L+1, F) frontier
    buffer as carried state (XLA keeps it on-chip), so the traced step
    body — and with it every DMA pipeline of the streamed tier — appears
    once instead of L times; inside the step the rule-trie descent and
    term fan-out stay unrolled over their static widths with masked
    out-of-range lanes, exactly the reference DP's shape.
    """
    bq = q.shape[0]
    F, L, M, E = frontier, seq_len, rule_matches, edit_budget
    BW = branch_width

    # write-back discipline (mirrors the jnp reference): each completed
    # row is expanded — teleports + delete closure — exactly once, as the
    # last write of the step that completes it, so step i reads buf[:, i]
    # ready-made.  Equivalent to the old expand-at-read style: every
    # write into row i+1 has landed by the end of step i, and
    # re-expanding an expanded row changes nothing and drops nothing.
    buf0 = jnp.full((bq, L + 1, F), _NEG_ONE, jnp.int32).at[:, 0, 0].set(0)
    ov0 = jnp.zeros((bq,), jnp.int32)
    if has_tele or E > 0:
        row0, drop0 = _expand_frontier(tabs, buf0[:, 0, :], F, E, BW,
                                       has_tele)
        buf0 = buf0.at[:, 0, :].set(row0)
        ov0 += drop0
    # query extended with -1s so the rule descent can probe past the end
    # of short suffixes (a -1 char kills the walk, like the reference's)
    qx = jnp.concatenate(
        [q, jnp.full((bq, max(max_lhs_len, 1)), _NEG_ONE, jnp.int32)],
        axis=1)

    def at_col(mat, i):
        return jax.lax.dynamic_slice(mat, (0, i), (bq, 1))[:, 0]

    def buf_row(buf, i):
        return jax.lax.dynamic_slice(buf, (0, i, 0), (bq, 1, F))[:, 0, :]

    def buf_put(buf, i, row):
        return jax.lax.dynamic_update_slice(buf, row[:, None, :], (0, i, 0))

    def step(i, carry):
        buf, overflow = carry
        row = buf_row(buf, i)
        c = at_col(q, i)
        nodes, d = _dec(row, E)

        # literal char step: dict children + synonym-branch children
        parts = [_enc(tabs.dict_children(nodes, c[:, None]), d, E)]
        if has_syn:
            parts.append(_enc(tabs.syn_children(nodes, c[:, None]), d, E))
        if E > 0:
            # substitute: any dict child whose edge char differs from c,
            # at d+1 (matching children already ride the literal part)
            wchars, wchildren = tabs.dict_child_window(nodes, BW)
            can = (c[:, None] >= 0) & (d < E)
            s_ok = can[..., None] & (wchildren >= 0) \
                & (wchars != c[:, None, None])
            parts.append(_enc(jnp.where(s_ok, wchildren, _NEG_ONE),
                              (d + 1)[..., None], E).reshape(bq, -1))
            # insert: stay put at d+1; synonym-branch chars must be typed
            # exactly, so mid-variant nodes don't absorb inserted chars
            n0 = jnp.where(nodes >= 0, nodes, 0)
            i_ok = can & (nodes >= 0) & (tabs.syn_mask_of(n0) == 0)
            parts.append(_enc(jnp.where(i_ok, nodes, _NEG_ONE), d + 1, E))
        merged, drop = _dedup(
            jnp.concatenate([buf_row(buf, i + 1)] + parts, axis=1), F)
        overflow += drop
        buf = buf_put(buf, i + 1, merged)

        # rule steps: inline rule-trie descent from position i; a full-lhs
        # match at depth j lands at the frontier row i + j + 1 (descents
        # running past the query end read the -1 extension and die).
        # Anchors must be dict nodes; the edit count carries through
        if M > 0:
            amask = (nodes >= 0) & \
                (tabs.syn_mask_of(jnp.where(nodes >= 0, nodes, 0)) == 0)
            anchors = jnp.where(amask, nodes, _NEG_ONE)
            node = jnp.zeros((bq,), jnp.int32)       # rule-trie root
            cnt = jnp.zeros((bq,), jnp.int32)
            for j in range(max_lhs_len):
                node = _csr_children(rfc, rec, rechild, node,
                                     at_col(qx, i + j))
                ok = node >= 0
                terms = _plane_rows(rterm,
                                    jnp.where(ok, node, 0))  # [BQ, Tw]
                end = jnp.clip(i + j + 1, 0, L)
                for j2 in range(max_terms):
                    rid = terms[:, j2]
                    has = ok & (rid >= 0) & (cnt < M)
                    cnt = jnp.where(has, cnt + 1, cnt)
                    if has_links:
                        tgt = tabs.link_lookup(anchors, rid)
                        tgt = jnp.where(has[:, None], tgt, _NEG_ONE)
                        tgt = _enc(tgt, d, E)
                    else:
                        tgt = jnp.full((bq, F), _NEG_ONE, jnp.int32)
                    dst = buf_row(buf, end)
                    merged, drop = _dedup(
                        jnp.concatenate([dst, tgt], axis=1), F)
                    any_tgt = (tgt >= 0).any(axis=1)
                    merged = jnp.where(any_tgt[:, None], merged, dst)
                    overflow += jnp.where(any_tgt, drop, 0)
                    buf = buf_put(buf, end, merged)

        # write-back: row i+1 is complete (rule ends are > i), expand it
        if has_tele or E > 0:
            nxt = buf_row(buf, i + 1)
            nxt, drop = _expand_frontier(tabs, nxt, F, E, BW, has_tele)
            overflow += drop
            buf = buf_put(buf, i + 1, nxt)
        return buf, overflow

    buf, overflow = jax.lax.fori_loop(0, L, step, (buf0, ov0))

    # final frontier: the row at each query's own length (already
    # expanded by the write-back discipline), decoded to plain node ids
    sel = jnp.broadcast_to(jnp.clip(qlen, 0, L)[:, None, None], (bq, 1, F))
    row = jnp.take_along_axis(buf, sel, axis=1)[:, 0, :]
    row = _dec(row, E)[0]

    # finalize: strict semantics drop mid-variant (synonym) loci, then
    # antichain reduction over preorder intervals [id, tout)
    is_syn = tabs.syn_mask_of(jnp.where(row >= 0, row, 0))
    row = jnp.where((row >= 0) & (is_syn == 0), row, _NEG_ONE)
    row, _ = _dedup(row, F)
    tin = jnp.where(row >= 0, row, _NEG_ONE)
    to = tabs.tout_of(jnp.where(row >= 0, row, 0))
    tin_i, tin_j = tin[:, :, None], tin[:, None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (bq, F, F), 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, (bq, F, F), 2)
    covered = ((tin_j <= tin_i) & (tin_i < to[:, None, :]) & (ii != jj)
               & (tin_j >= 0) & (tin_i >= 0)).any(axis=2)
    loci_ref[...] = jnp.where(covered, _NEG_ONE, row)
    ov_ref[:, 0] = overflow


def _kernel(fc_ref, ec_ref, echild_ref,
            sfc_ref, sec_ref, sechild_ref,
            syn_mask_ref, tout_ref, tele_ref,
            lptr_ref, lrule_ref, ltgt_ref,
            rfc_ref, rec_ref, rechild_ref, rterm_ref,
            q_ref, qlen_ref,
            loci_ref, ov_ref, **statics):
    tabs = _ResidentTables(
        fc_ref[...], ec_ref[...], echild_ref[...],
        sfc_ref[...], sec_ref[...], sechild_ref[...],
        syn_mask_ref[...], tout_ref[...], tele_ref[...],
        lptr_ref[...], lrule_ref[...], ltgt_ref[...])
    _sweep(tabs, rfc_ref[...], rec_ref[...], rechild_ref[...], rterm_ref[...],
           q_ref[...], qlen_ref[:, 0], loci_ref, ov_ref, **statics)


def _kernel_streamed(fc_hbm, ec_hbm, echild_hbm,
                     sfc_hbm, sec_hbm, sechild_hbm,
                     syn_mask_hbm, tout_hbm, tele_hbm,
                     lptr_hbm, lrule_hbm, ltgt_hbm,
                     rfc_ref, rec_ref, rechild_ref, rterm_ref,
                     q_ref, qlen_ref,
                     loci_ref, ov_ref,
                     pair_buf, word_buf, w1_buf, w2_buf, tele_buf,
                     sem_p, sem_w, sem_1, sem_2, sem_t, *,
                     walk_tile: int, link_tile: int, **statics):
    walk_iters = max(1, walk_tile.bit_length())
    link_iters = max(1, link_tile.bit_length())
    tw = int(tele_buf.shape[-1])
    tabs = _StreamedTables(
        StreamTable(fc_hbm, pair_buf, sem_p, 2),
        StreamTable(ec_hbm, w1_buf, sem_1, walk_tile),
        StreamTable(echild_hbm, w2_buf, sem_2, walk_tile),
        StreamTable(sfc_hbm, pair_buf, sem_p, 2),
        StreamTable(sec_hbm, w1_buf, sem_1, walk_tile),
        StreamTable(sechild_hbm, w2_buf, sem_2, walk_tile),
        StreamTable(syn_mask_hbm, word_buf, sem_w, 1),
        StreamTable(tout_hbm, word_buf, sem_w, 1),
        StreamTable(tele_hbm, tele_buf, sem_t, tw),
        StreamTable(lptr_hbm, pair_buf, sem_p, 2),
        StreamTable(lrule_hbm, w1_buf, sem_1, link_tile),
        StreamTable(ltgt_hbm, w2_buf, sem_2, link_tile),
        walk_iters, link_iters)
    _sweep(tabs, rfc_ref[...], rec_ref[...], rechild_ref[...], rterm_ref[...],
           q_ref[...], qlen_ref[:, 0], loci_ref, ov_ref, **statics)


def _kernel_packed(lbl_ref, flg_ref, c_ids_ref, c_tout_ref,
                   b_ids_ref, b_ptr_ref, b_char_ref, b_child_ref,
                   sb_ids_ref, sb_ptr_ref, sb_char_ref, sb_child_ref,
                   t_ids_ref, tele_ref, la_ids_ref, la_ptr_ref,
                   lrule_ref, ltgt_ref,
                   rfc_ref, rec_ref, rechild_ref, rterm_ref,
                   q_ref, qlen_ref,
                   loci_ref, ov_ref, **statics):
    tabs = _PackedResidentTables(
        lbl_ref[...], flg_ref[...], c_ids_ref[...], c_tout_ref[...],
        b_ids_ref[...], b_ptr_ref[...], b_char_ref[...], b_child_ref[...],
        sb_ids_ref[...], sb_ptr_ref[...], sb_char_ref[...],
        sb_child_ref[...], t_ids_ref[...], tele_ref[...],
        la_ids_ref[...], la_ptr_ref[...], lrule_ref[...], ltgt_ref[...])
    _sweep(tabs, rfc_ref[...], rec_ref[...], rechild_ref[...], rterm_ref[...],
           q_ref[...], qlen_ref[:, 0], loci_ref, ov_ref, **statics)


def _kernel_packed_streamed(lbl_hbm, flg_hbm, c_ids_ref, c_tout_ref,
                            b_ids_ref, b_ptr_ref, b_char_ref, b_child_ref,
                            sb_ids_ref, sb_ptr_ref, sb_char_ref,
                            sb_child_ref, t_ids_ref, tele_ref,
                            la_ids_ref, la_ptr_ref, lrule_ref, ltgt_ref,
                            rfc_ref, rec_ref, rechild_ref, rterm_ref,
                            q_ref, qlen_ref,
                            loci_ref, ov_ref,
                            lbl_buf, flg_buf, sem_l, sem_f, **statics):
    tabs = _PackedStreamedTables(
        StreamTable(lbl_hbm, lbl_buf, sem_l, 1),
        StreamTable(flg_hbm, flg_buf, sem_f, 1),
        c_ids_ref[...], c_tout_ref[...],
        b_ids_ref[...], b_ptr_ref[...], b_char_ref[...], b_child_ref[...],
        sb_ids_ref[...], sb_ptr_ref[...], sb_char_ref[...],
        sb_child_ref[...], t_ids_ref[...], tele_ref[...],
        la_ids_ref[...], la_ptr_ref[...], lrule_ref[...], ltgt_ref[...])
    _sweep(tabs, rfc_ref[...], rec_ref[...], rechild_ref[...], rterm_ref[...],
           q_ref[...], qlen_ref[:, 0], loci_ref, ov_ref, **statics)


def _call(kernel, tables, table_specs, queries, qlens, scratch, *,
          frontier: int, block_q: int, interpret: bool):
    # qlens / overflow ride as [B, 1] columns: Mosaic tiles a rank-1
    # block only in multiples of 128 rows, a [block_q, 1] column
    # whenever block_q is a multiple of 8 (or the whole batch)
    bsz, seq_len = queries.shape
    col = pl.BlockSpec((block_q, 1), lambda i: (i, 0))
    loci, overflow = pl.pallas_call(
        kernel,
        grid=(bsz // block_q,),
        in_specs=table_specs + [
            pl.BlockSpec((block_q, seq_len), lambda i: (i, 0)), col],
        out_specs=[pl.BlockSpec((block_q, frontier), lambda i: (i, 0)), col],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, frontier), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*tables, queries, qlens.reshape(bsz, 1))
    return loci, overflow[:, 0]


@functools.partial(jax.jit, static_argnames=(
    "frontier", "rule_matches", "max_lhs_len", "max_terms", "has_syn",
    "has_tele", "has_links", "edit_budget", "branch_width", "block_q",
    "interpret"))
def locus_dp_walk(first_child, edge_char, edge_child,
                  s_first_child, s_edge_char, s_edge_child,
                  syn_mask, tout, tele_plane,
                  link_ptr, link_rule, link_target,
                  r_first_child, r_edge_char, r_edge_child, r_term_plane,
                  queries, qlens, *,
                  frontier: int, rule_matches: int, max_lhs_len: int,
                  max_terms: int, has_syn: bool, has_tele: bool,
                  has_links: bool, edit_budget: int = 0,
                  branch_width: int = 1, block_q: int = 8,
                  interpret: bool):
    """Fused locus DP over a query batch (VMEM-resident tables).

    queries int32[B, L] (-1 padded, B divisible by block_q; the wrapper in
    ops.py pads), qlens int32[B].  Tables are the DeviceTrie arrays with
    empties padded to length 1 (gated off by the ``has_*`` statics).
    Returns (loci[B, F] finalized antichains, overflow[B]) — bit-identical
    to ``jax.vmap(engine.locus.locus_dp)`` on the jnp substrate.
    """
    def full(a):
        shape = tuple(int(s) for s in a.shape)
        return pl.BlockSpec(shape, (lambda i: (0,) * len(shape)))

    kernel = functools.partial(
        _kernel, frontier=frontier, rule_matches=rule_matches,
        max_lhs_len=max_lhs_len, max_terms=max_terms, has_syn=has_syn,
        has_tele=has_tele, has_links=has_links, edit_budget=edit_budget,
        branch_width=branch_width, seq_len=int(queries.shape[1]))
    tables = [first_child, edge_char, edge_child,
              s_first_child, s_edge_char, s_edge_child,
              syn_mask, tout, tele_plane,
              link_ptr, link_rule, link_target,
              r_first_child, r_edge_char, r_edge_child, r_term_plane]
    return _call(kernel, tables, [full(a) for a in tables], queries, qlens,
                 [], frontier=frontier, block_q=block_q, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "frontier", "rule_matches", "max_lhs_len", "max_terms", "has_syn",
    "has_tele", "has_links", "edit_budget", "branch_width", "walk_tile",
    "link_tile", "block_q", "interpret"))
def locus_dp_walk_streamed(first_child, edge_char, edge_child,
                           s_first_child, s_edge_char, s_edge_child,
                           syn_mask, tout, tele_plane,
                           link_ptr, link_rule, link_target,
                           r_first_child, r_edge_char, r_edge_child,
                           r_term_plane,
                           queries, qlens, *,
                           frontier: int, rule_matches: int,
                           max_lhs_len: int, max_terms: int, has_syn: bool,
                           has_tele: bool, has_links: bool,
                           edit_budget: int = 0, branch_width: int = 1,
                           walk_tile: int = 8,
                           link_tile: int = 8, block_q: int = 8,
                           interpret: bool):
    """HBM-resident variant of :func:`locus_dp_walk`: same contract, same
    results, but the dictionary-sized tables stay in HBM and every access
    is a double-buffered windowed DMA.  ``walk_tile``/``link_tile`` are
    the static window widths from the tile-aligned layout
    (``EngineConfig``); the rule trie stays VMEM-resident (it is sized by
    the rule set, not the dictionary)."""
    def full(a):
        shape = tuple(int(s) for s in a.shape)
        return pl.BlockSpec(shape, (lambda i: (0,) * len(shape)))

    hbm = pl.BlockSpec(memory_space=pltpu.ANY)
    kernel = functools.partial(
        _kernel_streamed, frontier=frontier, rule_matches=rule_matches,
        max_lhs_len=max_lhs_len, max_terms=max_terms, has_syn=has_syn,
        has_tele=has_tele, has_links=has_links, edit_budget=edit_budget,
        branch_width=branch_width, walk_tile=walk_tile,
        link_tile=link_tile, seq_len=int(queries.shape[1]))
    tables = [first_child, edge_char, edge_child,
              s_first_child, s_edge_char, s_edge_child,
              syn_mask, tout, tele_plane,
              link_ptr, link_rule, link_target,
              r_first_child, r_edge_char, r_edge_child, r_term_plane]
    specs = [hbm] * 12 + [full(a) for a in tables[12:]]
    lanes = block_q * frontier
    wmax = max(walk_tile, link_tile)
    scratch = [
        pltpu.VMEM((lanes, 2), jnp.int32),            # pointer-pair stage
        pltpu.VMEM((lanes, 1), jnp.int32),            # scalar gathers
        pltpu.VMEM((lanes, wmax), jnp.int32),         # char/rule windows
        pltpu.VMEM((lanes, wmax), jnp.int32),         # child/target windows
        pltpu.VMEM((lanes, int(tele_plane.shape[1])), jnp.int32),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    return _call(kernel, tables, specs, queries, qlens, scratch,
                 frontier=frontier, block_q=block_q, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "frontier", "rule_matches", "max_lhs_len", "max_terms", "has_syn",
    "has_tele", "has_links", "edit_budget", "branch_width", "block_q",
    "interpret"))
def locus_dp_walk_packed(p_labels, p_flags, c_ids, c_tout,
                         b_ids, b_ptr, b_char, b_child,
                         sb_ids, sb_ptr, sb_char, sb_child,
                         t_ids, tele_plane, la_ids, la_ptr,
                         link_rule, link_target,
                         r_first_child, r_edge_char, r_edge_child,
                         r_term_plane, queries, qlens, *,
                         frontier: int, rule_matches: int, max_lhs_len: int,
                         max_terms: int, has_syn: bool, has_tele: bool,
                         has_links: bool, edit_budget: int = 0,
                         branch_width: int = 1, block_q: int = 8,
                         interpret: bool):
    """Fused locus DP over the compressed (packed) layout, every table
    VMEM-resident.  Same contract and bit-identical results as
    :func:`locus_dp_walk`; the table set is the packed one — u8
    labels/flags planes plus the sparse side tables (empties padded to
    one inert ``-1`` row by the ops wrapper, which no node id matches)."""
    def full(a):
        shape = tuple(int(s) for s in a.shape)
        return pl.BlockSpec(shape, (lambda i: (0,) * len(shape)))

    kernel = functools.partial(
        _kernel_packed, frontier=frontier, rule_matches=rule_matches,
        max_lhs_len=max_lhs_len, max_terms=max_terms, has_syn=has_syn,
        has_tele=has_tele, has_links=has_links, edit_budget=edit_budget,
        branch_width=branch_width, seq_len=int(queries.shape[1]))
    tables = [p_labels, p_flags, c_ids, c_tout,
              b_ids, b_ptr, b_char, b_child,
              sb_ids, sb_ptr, sb_char, sb_child,
              t_ids, tele_plane, la_ids, la_ptr, link_rule, link_target,
              r_first_child, r_edge_char, r_edge_child, r_term_plane]
    return _call(kernel, tables, [full(a) for a in tables], queries, qlens,
                 [], frontier=frontier, block_q=block_q, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "frontier", "rule_matches", "max_lhs_len", "max_terms", "has_syn",
    "has_tele", "has_links", "edit_budget", "branch_width", "block_q",
    "interpret"))
def locus_dp_walk_packed_streamed(p_labels, p_flags, c_ids, c_tout,
                                  b_ids, b_ptr, b_char, b_child,
                                  sb_ids, sb_ptr, sb_char, sb_child,
                                  t_ids, tele_plane, la_ids, la_ptr,
                                  link_rule, link_target,
                                  r_first_child, r_edge_char, r_edge_child,
                                  r_term_plane, queries, qlens, *,
                                  frontier: int, rule_matches: int,
                                  max_lhs_len: int, max_terms: int,
                                  has_syn: bool, has_tele: bool,
                                  has_links: bool, edit_budget: int = 0,
                                  branch_width: int = 1, block_q: int = 8,
                                  interpret: bool):
    """HBM-resident variant of :func:`locus_dp_walk_packed`: only the two
    N-sized u8 planes (labels/flags) stay in HBM and stream per access as
    width-1 windows through their own u8 staging buffers; the sparse side
    tables and the rule trie — branch-count-sized — stay VMEM-resident.
    No stream-tile parameter: the packed layout's windows are single
    elements, so the tile-aligned layout plays no role here."""
    def full(a):
        shape = tuple(int(s) for s in a.shape)
        return pl.BlockSpec(shape, (lambda i: (0,) * len(shape)))

    hbm = pl.BlockSpec(memory_space=pltpu.ANY)
    kernel = functools.partial(
        _kernel_packed_streamed, frontier=frontier,
        rule_matches=rule_matches, max_lhs_len=max_lhs_len,
        max_terms=max_terms, has_syn=has_syn, has_tele=has_tele,
        has_links=has_links, edit_budget=edit_budget,
        branch_width=branch_width, seq_len=int(queries.shape[1]))
    tables = [p_labels, p_flags, c_ids, c_tout,
              b_ids, b_ptr, b_char, b_child,
              sb_ids, sb_ptr, sb_char, sb_child,
              t_ids, tele_plane, la_ids, la_ptr, link_rule, link_target,
              r_first_child, r_edge_char, r_edge_child, r_term_plane]
    specs = [hbm] * 2 + [full(a) for a in tables[2:]]
    lanes = block_q * frontier
    scratch = [
        pltpu.VMEM((lanes, 1), jnp.uint8),   # label window stage
        pltpu.VMEM((lanes, 1), jnp.uint8),   # flag window stage
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    return _call(kernel, tables, specs, queries, qlens, scratch,
                 frontier=frontier, block_q=block_q, interpret=interpret)
