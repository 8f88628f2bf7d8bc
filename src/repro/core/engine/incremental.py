"""Phase 1' — incremental locus DP (stateful per-keystroke sessions).

One keystroke extends the carried frontier by a single char-step instead
of re-running the full locus DP over the prefix.  All inner lookups and
compactions thread through the active substrate, so a session opened on a
``pallas``-substrate index runs its per-keystroke top-k through the same
kernels as the one-shot batch path.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import packed as pk
from repro.core.engine.locus import (decode_states, dict_child_window,
                                     encode_states, expand_frontier,
                                     finalize_loci, link_lookup)
from repro.core.engine.primitives import iters_for, note_path, resolve_sub
from repro.core.engine.structs import DeviceTrie, EngineConfig, NEG_ONE


class LocusState(NamedTuple):
    """Resumable locus-DP state after consuming some prefix.

    rows[0] is the teleport-expanded frontier for the full prefix; rows[j]
    (j < max_lhs_len) is the frontier j keystrokes ago.  The history window
    is required because a synonym rule whose lhs ends at the newest char
    anchors at the frontier of the position where the lhs *started*.
    rnodes[j] is the rule-trie node for the walk over the last j+1 chars
    (-1 once the walk dies), so full-lhs matches ending at the newest char
    are recognised without rescanning the prefix.
    """

    rows: jax.Array      # int32[H, F] expanded frontier rows, newest first
    rnodes: jax.Array    # int32[H]   rule-trie suffix walks, shortest first
    overflow: jax.Array  # int32      accumulated frontier drops (0 => exact)
    length: jax.Array    # int32      chars consumed


def init_locus_state(t: DeviceTrie, cfg: EngineConfig, sub=None) -> LocusState:
    """State for the empty prefix (locus = expanded root)."""
    sub = resolve_sub(cfg, sub)
    F = cfg.frontier
    H = max(cfg.max_lhs_len, 1)
    row = jnp.full((F,), NEG_ONE, jnp.int32).at[0].set(0)
    row, drop = expand_frontier(t, cfg, row, sub)
    rows = jnp.full((H, F), NEG_ONE, jnp.int32).at[0].set(row)
    return LocusState(rows=rows,
                      rnodes=jnp.full((H,), NEG_ONE, jnp.int32),
                      overflow=jnp.int32(0) + drop,
                      length=jnp.int32(0))


def advance_locus_state(t: DeviceTrie, cfg: EngineConfig, state: LocusState,
                        c, sub=None) -> LocusState:
    """One keystroke: extend the frontier by char ``c`` (no-op when c < 0).

    Equivalent to one step of ``locus_dp`` — literal dict/synonym-branch
    children of the current frontier, plus link-store steps for every rule
    whose lhs ends exactly at the new char — but reuses the carried frontier
    instead of rescanning the prefix.
    """
    sub = resolve_sub(cfg, sub)
    note_path("advance", "jnp")   # no substrate has a kernel for it
    F = cfg.frontier
    E = cfg.edit_budget
    H = state.rows.shape[0]
    c = jnp.asarray(c, jnp.int32)
    row = state.rows[0]
    nodes, d = decode_states(row, E)

    packed = pk.is_packed(t)
    if packed:
        parts = [encode_states(pk.dict_children(t, nodes, c), d, E)]
        if pk.has_syn_edges(t):
            parts.append(encode_states(pk.syn_children(t, nodes, c), d, E))
    else:
        d_iters = iters_for(int(t.edge_char.shape[0]))
        parts = [encode_states(
            sub.csr_child_lookup(t.first_child, t.edge_char,
                                 t.edge_child, nodes, c, d_iters), d, E)]
        if int(t.s_edge_child.shape[0]) > 0:
            s_iters = iters_for(int(t.s_edge_char.shape[0]))
            parts.append(encode_states(
                sub.csr_child_lookup(t.s_first_child, t.s_edge_char,
                                     t.s_edge_child, nodes, c, s_iters),
                d, E))
    if E > 0:
        # bounded-edit keystroke transitions (mirror locus_dp's step):
        # substitute into any non-matching dict child / insert in place,
        # both at d+1; delete closure rides expand_frontier below
        wchars, wchildren = dict_child_window(t, cfg, nodes)
        can = (c >= 0) & (d < E)
        s_ok = can[:, None] & (wchildren >= 0) & (wchars != c)
        parts.append(encode_states(
            jnp.where(s_ok, wchildren, NEG_ONE),
            (d + 1)[:, None], E).reshape(-1))
        n0 = jnp.where(nodes >= 0, nodes, 0)
        is_syn = pk.syn_mask_of(t, n0) if packed else t.syn_mask[n0]
        i_ok = can & (nodes >= 0) & ~is_syn
        parts.append(encode_states(
            jnp.where(i_ok, nodes, NEG_ONE), d + 1, E))

    rnodes = state.rnodes
    if cfg.rule_matches > 0 and cfg.max_lhs_len > 0:
        r_iters = iters_for(int(t.r_edge_char.shape[0]))
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  state.rnodes[:-1]])
        rnodes = sub.csr_child_lookup(t.r_first_child, t.r_edge_char,
                                      t.r_edge_child, starts, c, r_iters)
        for j in range(H):
            node = rnodes[j]
            ok = node >= 0
            nn = jnp.where(ok, node, 0)
            terms = t.r_term_plane[nn]          # [term_width], -1 padded
            # lhs of length j+1 anchors at the frontier j keystrokes back
            anchor_row = state.rows[j]
            a_nodes, a_d = decode_states(anchor_row, E)
            anchor_ok = a_nodes >= 0
            an = jnp.where(anchor_ok, a_nodes, 0)
            anchor_ok &= ~(pk.syn_mask_of(t, an) if packed
                           else t.syn_mask[an])
            anchors = jnp.where(anchor_ok, a_nodes, NEG_ONE)
            for j2 in range(cfg.max_terms_per_node):
                rid = terms[j2]
                has = ok & (rid >= 0)
                tgt = link_lookup(t, anchors, rid)
                parts.append(encode_states(
                    jnp.where(has, tgt, NEG_ONE), a_d, E))

    merged, d1 = sub.dedup_compact(jnp.concatenate(parts), F)
    merged, d2 = expand_frontier(t, cfg, merged, sub)
    new_rows = jnp.concatenate([merged[None], state.rows[:-1]], axis=0)
    ok = c >= 0
    return LocusState(
        rows=jnp.where(ok, new_rows, state.rows),
        rnodes=jnp.where(ok, rnodes, state.rnodes),
        overflow=state.overflow + jnp.where(ok, d1 + d2, 0),
        length=state.length + jnp.where(ok, 1, 0),
    )


def advance_loci(t: DeviceTrie, cfg: EngineConfig, state: LocusState,
                 chars: jax.Array, sub=None) -> LocusState:
    """Extend the state by a fixed-shape char vector (-1 entries ignored)."""
    sub = resolve_sub(cfg, sub)

    def step(s, c):
        return advance_locus_state(t, cfg, s, c, sub), None

    state, _ = jax.lax.scan(step, state, jnp.asarray(chars, jnp.int32))
    return state


def init_locus_batch(t: DeviceTrie, cfg: EngineConfig, batch: int,
                     sub=None) -> LocusState:
    """Stacked state [batch, ...] of ``batch`` empty-prefix sessions.

    The continuous-batching scheduler's *slab*: every lane starts at the
    expanded root, bit-identical to ``init_locus_state`` per lane."""
    state = init_locus_state(t, cfg, sub)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (batch,) + x.shape), state)


def advance_loci_batch(t: DeviceTrie, cfg: EngineConfig, states: LocusState,
                       chars: jax.Array, sub=None) -> LocusState:
    """One keystroke per lane across a stacked state batch.

    ``states`` is a LocusState whose leaves carry a leading batch dim;
    ``chars`` is int32[B].  Lanes with ``chars < 0`` are untouched (the
    single-state no-op contract of :func:`advance_locus_state`), so a
    partially filled micro-batch block advances only its live lanes in
    one dispatch.  Per-lane results are bit-identical to the sequential
    :func:`advance_locus_state` — lanes never interact (pure vmap)."""
    sub = resolve_sub(cfg, sub)
    return jax.vmap(
        lambda s, c: advance_locus_state(t, cfg, s, c, sub))(
        states, jnp.asarray(chars, jnp.int32))


def topk_from_loci(t: DeviceTrie, cfg: EngineConfig, state: LocusState,
                   k: int, sub=None):
    """Top-k for the prefix carried by ``state`` (scores, sids, exact)."""
    from repro.core.engine.substrate import topk_phase2

    sub = resolve_sub(cfg, sub)
    loci = finalize_loci(t, decode_states(state.rows[0], cfg.edit_budget)[0])
    scores, sids, exact = topk_phase2(t, cfg, loci, k, sub)
    return scores, sids, exact & (state.overflow == 0)


def topk_from_loci_batch(t: DeviceTrie, cfg: EngineConfig,
                         states: LocusState, k: int, sub=None):
    """Top-k for every lane of a stacked state batch in one dispatch:
    (scores[B, k], sids[B, k], exact[B]).

    Phase 2 goes through the substrate's natively batched path
    (``beam_topk_batch`` / ``cached_topk_batch``) — the same kernels the
    one-shot ``complete_batch`` uses — so a coalesced micro-batch of
    keystrokes pays one kernel launch instead of B."""
    from repro.core.engine.substrate import topk_phase2_batch

    sub = resolve_sub(cfg, sub)
    loci = jax.vmap(lambda row: finalize_loci(
        t, decode_states(row, cfg.edit_budget)[0]))(states.rows[:, 0])
    scores, sids, exact = topk_phase2_batch(t, cfg, loci, k, sub)
    return scores, sids, exact & (states.overflow == 0)
