"""Phase 1 — locus DP: a fixed-width frontier sweep over query positions.

reach[pos] = set of trie nodes reachable by consuming p[:pos] under some
rewriting.  Transitions: literal char step (dict + synonym-branch
children), synonym teleports (ET/HT expanded rules), and rule steps
through the link store (TT/HT unexpanded rules).  All fixed shapes; the
rule-side lookups read the packed rule plane
(:func:`repro.core.trie_build.pack_rule_planes`): dense ``tele_plane``
rows for teleports, ``link_ptr`` + one binary search for link steps, and
``r_term_plane`` rows for full-lhs matches.

Bounded-edit mode (``cfg.edit_budget`` = E > 0) generalizes the frontier:
each entry becomes the packed state ``node * (E + 1) + edits_used`` and
the sweep gains three extra transition families on the dictionary side —
*substitute* (consume a query char into any non-matching dict child at
d+1), *insert* (consume a query char staying put at d+1) and *delete*
(take any dict child without consuming a query char, applied as an
E-round closure when a position's row completes).  Synonym-branch chars
and rule lhs occurrences must still be typed exactly; teleports and rule
steps carry the edit count through unchanged.  At E = 0 the packing and
every edit transition degenerate to the exact pre-edit computation, so
results (including overflow counts) are bit-identical.

Every inner CSR lookup / dedup-compaction routes through the active
:class:`~repro.core.engine.substrate.Substrate` (threaded as ``sub``), so
kernel-backed substrates can replace the primitives without touching the
DP structure.  Substrates may also replace this whole sweep at batch
granularity (``Substrate.walk_batch``) — the Pallas trie-walk kernel
handles the rule-free prefix case and the fused locus-DP kernel
(:mod:`repro.kernels.locus_dp`) the rule-bearing tt/et/ht case, both
bit-identical to this reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import packed as pk
from repro.core.engine.primitives import (dedup_pad, iters_for, lower_bound,
                                          resolve_sub)
from repro.core.engine.structs import DeviceTrie, EngineConfig, NEG_ONE


def match_table(t: DeviceTrie, cfg: EngineConfig, q: jax.Array, sub=None):
    """All full-lhs rule matches per query position.

    Returns (rule[L, M], end[L, M]) with -1 padding; end = pos + len(lhs).
    """
    sub = resolve_sub(cfg, sub)
    L = q.shape[0]
    M = cfg.rule_matches
    if M == 0:
        z = jnp.full((L, 1), NEG_ONE, jnp.int32)
        return z, z
    iters = iters_for(int(t.r_edge_char.shape[0]))
    qx = jnp.concatenate([q, jnp.full((cfg.max_lhs_len,), NEG_ONE, jnp.int32)])

    def at_pos(i):
        rules = jnp.full((M,), NEG_ONE, jnp.int32)
        ends = jnp.full((M,), NEG_ONE, jnp.int32)
        node = jnp.int32(0)
        cnt = jnp.int32(0)
        for j in range(cfg.max_lhs_len):
            c = jax.lax.dynamic_index_in_dim(qx, i + j, keepdims=False)
            node = sub.csr_child_lookup(
                t.r_first_child, t.r_edge_char, t.r_edge_child,
                node[None], c[None], iters)[0]
            ok = node >= 0
            nn = jnp.where(ok, node, 0)
            terms = t.r_term_plane[nn]          # [term_width], -1 padded
            for j2 in range(cfg.max_terms_per_node):
                rid = terms[j2]
                has = ok & (rid >= 0) & (cnt < M)
                slot = jnp.clip(cnt, 0, M - 1)
                rules = jnp.where(has, rules.at[slot].set(rid), rules)
                ends = jnp.where(has, ends.at[slot].set(i + j + 1), ends)
                cnt = jnp.where(has, cnt + 1, cnt)
        return rules, ends

    return jax.vmap(at_pos)(jnp.arange(L, dtype=jnp.int32))


def encode_states(nodes: jax.Array, d, E: int) -> jax.Array:
    """Pack (node, edits-used d) into one frontier entry: node*(E+1)+d.
    Identity at E=0, so exact-mode traces are untouched; -1 stays -1."""
    if E == 0:
        return nodes
    return jnp.where(nodes < 0, NEG_ONE, nodes * (E + 1) + d)


def decode_states(states: jax.Array, E: int):
    """Inverse of :func:`encode_states`: (nodes, d); -1 -> (-1, 0)."""
    if E == 0:
        return states, jnp.zeros_like(states)
    nodes = jnp.where(states < 0, NEG_ONE, states // (E + 1))
    d = jnp.where(states < 0, 0, states % (E + 1))
    return nodes, d


def dict_child_window(t: DeviceTrie, cfg: EngineConfig, nodes: jax.Array):
    """All dict children of each node: (chars, children) [..., BW] with
    BW = cfg.branch_width (static max dict fanout), -1 padded.  Feeds the
    substitute/delete edit transitions, which need *every* child rather
    than the one matching a char."""
    if pk.is_packed(t):
        return pk.dict_child_window(t, nodes, cfg.branch_width)
    BW = cfg.branch_width
    shape = tuple(nodes.shape) + (BW,)
    if int(t.edge_char.shape[0]) == 0:
        z = jnp.full(shape, NEG_ONE, jnp.int32)
        return z, z
    valid = nodes >= 0
    n = jnp.where(valid, nodes, 0)
    lo = t.first_child[n]
    cnt = jnp.where(valid, t.first_child[n + 1] - lo, 0)
    js = jnp.arange(BW, dtype=jnp.int32)
    idx = jnp.clip(lo[..., None] + js, 0, int(t.edge_char.shape[0]) - 1)
    m = js < cnt[..., None]
    chars = jnp.where(m, t.edge_char[idx], NEG_ONE)
    children = jnp.where(m, t.edge_child[idx], NEG_ONE)
    return chars, children


def teleport_expand(t: DeviceTrie, cfg: EngineConfig, row: jax.Array,
                    sub=None):
    """row [F] -> row plus teleport targets, dedup'd back to [F].  In
    bounded-edit mode the row carries packed states: targets inherit the
    source state's edit count."""
    if cfg.teleports == 0:
        return row, jnp.int32(0)
    sub = resolve_sub(cfg, sub)
    F = row.shape[0]
    E = cfg.edit_budget
    nodes, d = decode_states(row, E)
    if pk.is_packed(t):
        tgt = pk.tele_rows(t, nodes)
    else:
        valid = nodes >= 0
        n = jnp.where(valid, nodes, 0)
        tgt = jnp.where(valid[:, None], t.tele_plane[n], NEG_ONE)
    tgt = encode_states(tgt, d[:, None], E)
    merged = jnp.concatenate([row, tgt.reshape(-1)])
    return sub.dedup_compact(merged, F)


def delete_close(t: DeviceTrie, cfg: EngineConfig, row: jax.Array,
                 sub=None):
    """Bounded-edit delete closure: E rounds of "take any dict child at
    d+1 without consuming a query char" over a frontier row.  E static
    rounds reach the fixpoint because each round raises d and d < E gates
    the step.  No-op (0 drops) at E=0."""
    E = cfg.edit_budget
    if E == 0:
        return row, jnp.int32(0)
    sub = resolve_sub(cfg, sub)
    F = row.shape[0]
    drop_total = jnp.int32(0)
    for _ in range(E):
        nodes, d = decode_states(row, E)
        _, children = dict_child_window(t, cfg, nodes)
        ok = (children >= 0) & (d < E)[:, None]
        tgt = encode_states(jnp.where(ok, children, NEG_ONE),
                            (d + 1)[:, None], E)
        row, drop = sub.dedup_compact(
            jnp.concatenate([row, tgt.reshape(-1)]), F)
        drop_total += drop
    return row, drop_total


def expand_frontier(t: DeviceTrie, cfg: EngineConfig, row: jax.Array,
                    sub=None):
    """Teleport expansion then delete closure — the combined fixpoint a
    row needs once all its position's contributions have landed.
    Teleports attach only to synonym nodes and delete steps only descend
    dict children (which never carry teleports), so one expansion
    followed by E delete rounds reaches the joint fixpoint."""
    sub = resolve_sub(cfg, sub)
    row, drop = teleport_expand(t, cfg, row, sub)
    row, drop2 = delete_close(t, cfg, row, sub)
    return row, drop + drop2


def link_lookup(t: DeviceTrie, anchors: jax.Array, rid: jax.Array):
    """Link-store search: (anchor, rule) -> target or -1. anchors [F].

    The packed ``link_ptr`` CSR bounds each anchor's (rule-sorted) row
    range with one pointer load, so the whole lookup is a single binary
    search over ``link_rule`` instead of the pre-relayout three."""
    if pk.is_packed(t):
        return pk.link_lookup(t, anchors, rid)
    n_link = int(t.link_rule.shape[0])
    if n_link == 0:
        return jnp.full(anchors.shape, NEG_ONE, jnp.int32)
    iters = iters_for(n_link)
    valid = anchors >= 0
    a = jnp.where(valid, anchors, 0)
    lo = t.link_ptr[a]
    hi = t.link_ptr[a + 1]
    pos = lower_bound(t.link_rule, lo, hi, rid, iters)
    found = (pos < hi) & (t.link_rule[jnp.clip(pos, 0, n_link - 1)] == rid) & valid
    return jnp.where(found, t.link_target[jnp.clip(pos, 0, n_link - 1)], NEG_ONE)


def finalize_loci(t: DeviceTrie, row: jax.Array) -> jax.Array:
    """Turn a (teleport-expanded) frontier row into the final locus antichain:
    drop mid-variant synonym nodes, dedup, and remove covered descendants."""
    F = row.shape[0]
    packed = pk.is_packed(t)
    # strict semantics: drop mid-variant (synonym) loci
    n0 = jnp.where(row >= 0, row, 0)
    is_syn = pk.syn_mask_of(t, n0) if packed else t.syn_mask[n0]
    row = jnp.where((row >= 0) & ~is_syn, row, NEG_ONE)
    row, _ = dedup_pad(row, F)
    # antichain reduction via preorder intervals: drop descendants
    tin = jnp.where(row >= 0, row, NEG_ONE)
    n0 = jnp.where(row >= 0, row, 0)
    to = pk.tout_of(t, n0) if packed else t.tout[n0]
    covered = (
        (tin[None, :] <= tin[:, None]) & (tin[:, None] < to[None, :])
        & (jnp.arange(F)[None, :] != jnp.arange(F)[:, None])
        & (row[None, :] >= 0) & (row[:, None] >= 0)
    ).any(axis=1)
    # ties: identical ids already removed by dedup; strict ancestor covers
    return jnp.where(covered, NEG_ONE, row)


def locus_dp(t: DeviceTrie, cfg: EngineConfig, q: jax.Array, qlen: jax.Array,
             sub=None):
    """Locus set after consuming the whole query under all rewritings.

    q: int32[L] (-1 padded), qlen: int32 scalar.
    Returns (loci[F] dict-node ids, -1 padded; overflow count int32).
    """
    sub = resolve_sub(cfg, sub)
    L = int(q.shape[0])
    F = cfg.frontier
    E = cfg.edit_budget
    packed = pk.is_packed(t)
    if packed:
        has_syn_edges = pk.has_syn_edges(t)
        d_iters = s_iters = 0
    else:
        d_iters = iters_for(int(t.edge_char.shape[0]))
        s_iters = iters_for(int(t.s_edge_char.shape[0]))
        has_syn_edges = int(t.s_edge_child.shape[0]) > 0
    M = cfg.rule_matches

    mrule, mend = match_table(t, cfg, q, sub)

    # write-back sweep: each completed row is expanded (teleports + delete
    # closure) exactly once, as the last write of the step that completes
    # it, so step i reads buf[i] ready-made.  Equivalent (content and
    # overflow) to expanding at read time: every write into row i+1 —
    # char/edit parts of step i, rule steps from positions <= i — has
    # landed by the end of step i, and re-expanding an expanded row
    # changes nothing and drops nothing.
    buf = jnp.full((L + 1, F), NEG_ONE, jnp.int32)
    buf = buf.at[0, 0].set(0)   # root at d=0 encodes to 0 for any E
    row0, drop0 = expand_frontier(t, cfg, buf[0], sub)
    buf = buf.at[0].set(row0)
    overflow = drop0

    def step(i, carry):
        buf, overflow = carry
        row = jax.lax.dynamic_slice(buf, (i, 0), (1, F))[0]
        c = jax.lax.dynamic_index_in_dim(q, i, keepdims=False)
        nodes, d = decode_states(row, E)

        # literal char step: dict children + synonym-branch children
        if packed:
            nd = pk.dict_children(t, nodes, c)
        else:
            nd = sub.csr_child_lookup(t.first_child, t.edge_char,
                                      t.edge_child, nodes, c, d_iters)
        parts = [encode_states(nd, d, E)]
        if has_syn_edges:
            if packed:
                ns = pk.syn_children(t, nodes, c)
            else:
                ns = sub.csr_child_lookup(t.s_first_child, t.s_edge_char,
                                          t.s_edge_child, nodes, c, s_iters)
            parts.append(encode_states(ns, d, E))
        if E > 0:
            # substitute: any dict child whose edge char differs from c,
            # at d+1 (matching children already ride the literal part)
            wchars, wchildren = dict_child_window(t, cfg, nodes)
            can = (c >= 0) & (d < E)
            s_ok = can[:, None] & (wchildren >= 0) & (wchars != c)
            parts.append(encode_states(
                jnp.where(s_ok, wchildren, NEG_ONE),
                (d + 1)[:, None], E).reshape(-1))
            # insert: the query has an extra char; stay put at d+1.
            # Synonym-branch chars must be typed exactly, so mid-variant
            # nodes don't absorb inserted chars
            n0 = jnp.where(nodes >= 0, nodes, 0)
            is_syn = pk.syn_mask_of(t, n0) if packed else t.syn_mask[n0]
            i_ok = can & (nodes >= 0) & ~is_syn
            parts.append(encode_states(
                jnp.where(i_ok, nodes, NEG_ONE), d + 1, E))
        nxt_row = jax.lax.dynamic_slice(buf, (i + 1, 0), (1, F))[0]
        merged, drop = sub.dedup_compact(jnp.concatenate([nxt_row] + parts), F)
        overflow += drop
        buf = jax.lax.dynamic_update_slice(buf, merged[None], (i + 1, 0))

        # rule steps through the link store (anchors must be dict nodes;
        # the lhs is typed exactly and the edit count carries through)
        if M > 0:
            anchor_ok = nodes >= 0
            ar = jnp.where(anchor_ok, nodes, 0)
            anchor_ok &= ~(pk.syn_mask_of(t, ar) if packed else t.syn_mask[ar])
            anchors = jnp.where(anchor_ok, nodes, NEG_ONE)
            rows = jnp.arange(L + 1, dtype=jnp.int32)
            for m in range(M):
                rid = mrule[i, m]
                end = mend[i, m]
                tgt = link_lookup(t, anchors, rid)
                tgt = jnp.where((rid >= 0), tgt, NEG_ONE)
                tgt = encode_states(tgt, d, E)
                # the destination row differs per query, so read and write
                # it with a one-hot select over the rows: under vmap a
                # dynamic slice / update at a per-lane index becomes a
                # gather / scatter, which XLA:TPU runs one lane at a time
                j = jnp.clip(jnp.where(end >= 0, end, 0), 0, L)
                sel = (rows == j)[:, None]
                dst = jnp.max(jnp.where(sel, buf, NEG_ONE), axis=0)
                merged, drop = sub.dedup_compact(jnp.concatenate([dst, tgt]), F)
                any_tgt = jnp.any(tgt >= 0)
                overflow += jnp.where(any_tgt, drop, 0)
                buf = jnp.where(sel & any_tgt, merged[None], buf)

        # write-back: row i+1 is complete (rule ends are > i), expand it
        nxt = jax.lax.dynamic_slice(buf, (i + 1, 0), (1, F))[0]
        nxt, drop = expand_frontier(t, cfg, nxt, sub)
        overflow += drop
        buf = jax.lax.dynamic_update_slice(buf, nxt[None], (i + 1, 0))
        return buf, overflow

    buf, overflow = jax.lax.fori_loop(0, L, step, (buf, overflow))

    row = jax.lax.dynamic_slice(buf, (jnp.clip(qlen, 0, L), 0), (1, F))[0]
    return finalize_loci(t, decode_states(row, E)[0]), overflow
