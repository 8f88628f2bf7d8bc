"""Pluggable execution substrates + the public completion entry points.

A :class:`Substrate` bundles the engine's hot primitives behind one seam:

  - ``csr_child_lookup`` / ``dedup_compact`` — the inner locus-DP ops
    (threaded through every frontier step);
  - ``walk_batch``       — phase 1 at batch granularity, with a
    ``can_walk_batch`` capability probe naming which (trie, config)
    shapes the substrate handles natively;
  - ``topk_with_payload`` — batched small-k selection with payload;
  - ``cached_topk_batch`` — the cached-top-K locus gather+merge;
  - ``beam_topk_batch``   — phase 2a at batch granularity, with a
    ``can_beam_batch`` capability probe naming which (trie, config, k)
    shapes the substrate handles natively.

The base class *is* the reference implementation (pure jnp, registered as
``"jnp"``).  :class:`PallasSubstrate` (``"pallas"``) routes the batched
walk through :func:`repro.kernels.ops.trie_walk` (rule-free tries) or the
fused synonym-aware locus-DP kernel :func:`repro.kernels.ops.locus_walk`
(tt/et/ht), beam phase 2 through the fused generator-pool priority-search
kernel :func:`repro.kernels.ops.beam_topk`, cached merges through
:func:`repro.kernels.ops.topk_select` / ``cached_topk_merge``, and runs
in interpret mode off-TPU.  ``EngineConfig.substrate`` names the
substrate, so it rides every jit/compile-cache key;
``resolve_substrate("auto")`` picks ``jnp`` on every backend, because it
is the only substrate whose whole path compiles for a TPU (see
:func:`resolve_substrate`).

With the fused beam kernel every hot phase — walk, beam, cached merge —
is substrate-pluggable.  Each fused kernel additionally runs in one of
two *tiers*: VMEM-resident tables, or the DMA-streamed tier for tries
whose tables outgrow the VMEM budget (``EngineConfig.memory_budget``) —
the ``walk_variant``/``beam_variant`` probes pick resident vs streamed
vs jnp-fallback per call.  Remaining kernel work (dedup-compaction)
lands as an additive substrate method override, not an engine rewrite.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.engine import beam, cached, locus, packed as pk, primitives
from repro.core.engine.structs import DeviceTrie, EngineConfig, NEG_ONE


class Substrate:
    """Reference (pure-jnp) execution substrate; the protocol other
    substrates subclass.  Stateless — one shared instance per registry
    entry."""

    name = "jnp"

    # -- locus-DP inner primitives ----------------------------------------

    def csr_child_lookup(self, ptr, chars, children, nodes, ch, iters: int):
        return primitives.csr_child_lookup(ptr, chars, children, nodes, ch,
                                           iters)

    def dedup_compact(self, vec: jax.Array, width: int):
        return primitives.dedup_pad(vec, width)

    # -- phase 1: batched locus walk --------------------------------------

    def can_walk_batch(self, t: DeviceTrie, cfg: EngineConfig,
                       seq_len: int) -> bool:
        """Capability probe: True when ``walk_batch`` has a native
        (non-fallback) path for this (trie, config, query length).  The
        jnp reference DP handles everything."""
        return True

    def walk_batch(self, t: DeviceTrie, cfg: EngineConfig, qs: jax.Array,
                   qlens: jax.Array):
        """qs int32[B, L] (-1 padded), qlens int32[B] ->
        (loci[B, F], overflow[B])."""
        primitives.note_path("walk", "jnp")
        return jax.vmap(
            lambda q, ql: locus.locus_dp(t, cfg, q, ql, self))(qs, qlens)

    # -- phase 2: top-k ----------------------------------------------------

    def topk_with_payload(self, scores: jax.Array, payload: jax.Array,
                          k: int):
        """scores/payload int32[B, C] -> (top_s[B, k], top_p[B, k]),
        score-descending, ties toward the lower candidate index."""
        primitives.note_path("top-k", "jnp")
        top_s, idx = jax.lax.top_k(scores, k)
        return top_s, jnp.take_along_axis(payload, idx, axis=1)

    def cached_topk_batch(self, t: DeviceTrie, cfg: EngineConfig,
                          loci: jax.Array, k: int):
        """Cached-top-K gather+merge: loci int32[B, F] ->
        (scores[B, k], sids[B, k], exact[B])."""
        assert cfg.use_cache and k <= cfg.cache_k, \
            "cache disabled or k too large"
        primitives.note_path("cached-merge", "jnp")
        flat_s, flat_i = cached.gather_cached(t, loci)
        s, p = self.topk_with_payload(flat_s, flat_i, k)
        return s, p, jnp.ones(loci.shape[:-1], bool)

    def can_beam_batch(self, t: DeviceTrie, cfg: EngineConfig,
                       k: int) -> bool:
        """Capability probe: True when ``beam_topk_batch`` has a native
        (non-fallback) path for this (trie, config, k).  The vmapped jnp
        reference handles everything."""
        return True

    def beam_topk_batch(self, t: DeviceTrie, cfg: EngineConfig,
                        loci: jax.Array, k: int):
        """Beam phase 2 over a locus batch: (scores[B,k], sids[B,k],
        exact[B])."""
        primitives.note_path("beam", "jnp")
        return jax.vmap(lambda l: beam.beam_topk(t, cfg, l, k))(loci)


class PallasSubstrate(Substrate):
    """Kernel-backed substrate: dispatches the batched hot primitives to
    :mod:`repro.kernels` (compiled on TPU, interpret mode elsewhere).

    Phase 1 has two kernel paths: rule-free tries take the single-node
    longest-prefix walk (``trie_walk``), rule-bearing tt/et/ht tries take
    the fused synonym-aware locus DP (``locus_walk``) whenever the static
    shapes fit the kernel (``can_walk_batch``); anything else falls back
    to the inherited jnp DP, which is bit-identical by contract.  The
    DP's *inner* lookups/compactions are likewise inherited — they only
    run on the fallback path, where a pallas_call cannot be tiled.

    Phase 2a (beam) takes the fused generator-pool priority-search kernel
    (``beam_topk``) whenever (W, P, k, max_steps) fit the
    ``can_beam_batch`` envelope; outside it — including the later rounds
    of the host-side doubled-width exactness retry, whose widths grow 4x
    per round — the inherited vmapped reference answers with identical
    results.

    Each kernel runs in one of two *tiers* chosen by the VMEM byte
    budget (``cfg.memory_budget``, default ``_DEFAULT_VMEM_BUDGET``):
    tables at or under the budget stay whole in VMEM (*resident*);
    larger tables stay in HBM and the *streamed* variants double-buffer
    pointer pairs / row windows / plane rows in via ``make_async_copy``
    (:mod:`repro.kernels.stream`) — so an oversized per-shard sub-trie
    keeps its fused kernels instead of falling back to jnp.
    ``walk_variant`` / ``beam_variant`` name the chosen tier.
    """

    name = "pallas"

    # default VMEM byte budget for table residency, used when
    # cfg.memory_budget == 0: tables at or under it run the resident
    # kernels (which must also leave VMEM room for the per-block scratch),
    # larger ones the DMA-streamed tier
    _DEFAULT_VMEM_BUDGET = 8 << 20

    # Mosaic's default scoped-VMEM limit per kernel (not the chip's
    # physical VMEM, which is larger on a v5e); a user-set memory_budget
    # is clamped here — a larger budget would declare tables "resident"
    # that a kernel under the default limit can never hold
    _VMEM_BYTES = 16 << 20

    # the DMA-streamed tier stages [lanes, tile] windows in VMEM scratch,
    # so the stream-tile widths (EngineConfig.walk_tile / link_tile /
    # emit_tile) and the teleport-plane width are part of the envelope:
    # beyond these the scratch alone would crowd out VMEM and the jnp
    # fallback is the right tool
    _STREAM_MAX_TILE = 1024

    # fused locus-DP static-shape envelope: beyond these the fused
    # sweep stops being a sensible single kernel (trace size / VMEM) and
    # the jnp DP is the right tool.  The per-step trip count grows as
    # max_lhs_len * max_terms_per_node, and the dedup width as
    # frontier * tele_width, so every one of those dimensions is bounded.
    # The envelope is shared by the resident and streamed tiers (the
    # sweep structure is identical; only table residency differs).
    _FUSE_MAX_SEQ = 64
    _FUSE_MAX_FRONTIER = 128
    _FUSE_MAX_RULE_MATCHES = 8
    _FUSE_MAX_LHS = 24
    _FUSE_MAX_TERMS = 4
    _FUSE_MAX_TELEPORTS = 16
    # bounded-edit additions: the edit budget multiplies the delete-closure
    # rounds per step, and the substitute/delete transitions stage
    # [lanes, branch_width] child windows in scratch, so both are bounded
    _FUSE_MAX_EDITS = 2
    _FUSE_MAX_BRANCH = 64

    # fused beam static-shape envelope: the selection network unrolls
    # W + P + k (argmax, mask) rounds per fixed-trip step, so the pool
    # and pop widths are bounded; max_steps is only the fori_loop trip
    # count but still caps the search the kernel is asked to run.  The
    # first doubled-width retry round (W x4) stays inside the envelope at
    # the default widths; later rounds fall back to the jnp reference.
    _BEAM_MAX_GENS = 256
    _BEAM_MAX_EXPAND = 32
    _BEAM_MAX_K = 64
    _BEAM_MAX_STEPS = 4096

    # table-byte accounting: the streamed locus-DP tier keeps the rule
    # trie resident (sized by the rule set, not the dictionary) and
    # streams everything dictionary-sized; the streamed beam tier
    # streams all five emission-side tables
    _WALK_STREAM_FIELDS = (
        "first_child", "edge_char", "edge_child", "s_first_child",
        "s_edge_char", "s_edge_child", "syn_mask", "tout", "tele_plane",
        "link_ptr", "link_rule", "link_target")
    _WALK_RESIDENT_FIELDS = (
        "r_first_child", "r_edge_char", "r_edge_child", "r_term_plane")
    _PREFIX_FIELDS = ("first_child", "edge_char", "edge_child")
    _BEAM_FIELDS = ("emit_ptr", "emit_node", "emit_score", "emit_is_leaf",
                    "leaf_sid")
    _CACHE_FIELDS = ("topk_score", "topk_sid")

    # compressed-layout (packed) counterparts: the streamed packed walk
    # streams only the two u8 per-node planes; every sparse side table —
    # chain representatives, branching rows, teleports, link spans — plus
    # the rule trie stays VMEM-resident (all are branch-count-sized, not
    # node-count-sized)
    _WALK_STREAM_FIELDS_PACKED = ("p_labels", "p_flags")
    _WALK_RESIDENT_FIELDS_PACKED = (
        "c_ids", "c_tout", "b_ids", "b_ptr", "b_char", "b_child",
        "sb_ids", "sb_ptr", "sb_char", "sb_child", "t_ids", "t_plane",
        "la_ids", "la_ptr", "link_rule", "link_target",
        "r_first_child", "r_edge_char", "r_edge_child", "r_term_plane")
    # p_labels rides both tuples for the is_packed layout probe even
    # though the kernels only read p_flags — the N extra u8 bytes keep
    # the accounting a (tiny) over-estimate instead of an under-count
    _BEAM_FIELDS_PACKED = (
        "p_labels", "p_flags", "c_ids", "c_eptr", "c_enode", "c_escore",
        "c_eleaf", "c_maxscore", "l_ids", "l_sid")
    _CACHE_FIELDS_PACKED = ("p_labels", "pc_score", "pc_base", "pc_sid",
                            "c_ids")

    def _budget(self, cfg: EngineConfig) -> int:
        budget = cfg.memory_budget or self._DEFAULT_VMEM_BUDGET
        return min(budget, self._VMEM_BYTES)

    @staticmethod
    def _table_bytes(t: DeviceTrie, fields) -> int:
        # itemsize-aware: the packed layout's u8/u16 tables count their
        # real footprint, which is the whole point of the compression
        return sum(math.prod(a.shape) * a.dtype.itemsize
                   for a in (getattr(t, f) for f in fields)
                   if a is not None)

    def min_streamed_budget(self, t: DeviceTrie) -> int:
        """The smallest ``memory_budget`` that still admits the streamed
        walk tier for this trie: room for the resident-side tables (the
        rule trie; for packed layouts also the sparse side tables) and
        nothing else.  Test/benchmark harnesses use it to *force* the
        streamed tier — every streamed table is over budget at this
        value."""
        fields = (self._WALK_RESIDENT_FIELDS_PACKED if pk.is_packed(t)
                  else self._WALK_RESIDENT_FIELDS)
        return max(self._table_bytes(t, fields), 1)

    @staticmethod
    def _rule_free(t: DeviceTrie, cfg: EngineConfig) -> bool:
        """True when the walk is a pure prefix descent (plain kind, or a
        rule-free build): no link store, no teleports, no synonym edges —
        the frontier then never holds more than one node.  A nonzero edit
        budget breaks the single-node invariant, so edit-mode walks always
        take the full DP (fused sweep or jnp reference)."""
        return (cfg.rule_matches == 0 and cfg.teleports == 0
                and int(t.s_edge_child.shape[0]) == 0
                and cfg.edit_budget == 0)

    def _fuse_shapes_ok(self, cfg: EngineConfig, seq_len: int) -> bool:
        """The fused locus-DP kernel's static shape envelope (both tiers)."""
        return not (seq_len > self._FUSE_MAX_SEQ
                    or cfg.frontier > self._FUSE_MAX_FRONTIER
                    or cfg.rule_matches > self._FUSE_MAX_RULE_MATCHES
                    or cfg.max_lhs_len > self._FUSE_MAX_LHS
                    or cfg.max_terms_per_node > self._FUSE_MAX_TERMS
                    or cfg.teleports > self._FUSE_MAX_TELEPORTS
                    or cfg.tele_width > self._FUSE_MAX_TELEPORTS
                    or cfg.term_width > self._FUSE_MAX_TERMS
                    or cfg.edit_budget > self._FUSE_MAX_EDITS
                    or cfg.branch_width > self._FUSE_MAX_BRANCH)

    def walk_variant(self, t: DeviceTrie, cfg: EngineConfig,
                     seq_len: int) -> str | None:
        """Which native walk path serves this (trie, config, length):
        ``"resident"`` (tables fit the VMEM budget), ``"streamed"``
        (HBM tables behind the DMA tier), or ``None`` (jnp fallback —
        static shapes outside the kernel envelope)."""
        budget = self._budget(cfg)
        if pk.is_packed(t):
            # compressed layout: always the fused locus kernel (the
            # rule-free walk shortcut's dense CSR is elided); the
            # streamed tier's windows are width-1 u8 gathers, so the
            # stream-tile envelope does not apply
            if not self._fuse_shapes_ok(cfg, seq_len):
                return None
            resident = self._table_bytes(
                t, self._WALK_RESIDENT_FIELDS_PACKED)
            total = resident + self._table_bytes(
                t, self._WALK_STREAM_FIELDS_PACKED)
            if total <= budget:
                return "resident"
            return "streamed" if resident <= budget else None
        # the streamed tier stages [lanes, tile]-wide windows in VMEM
        # scratch, so the stream-tile widths are part of its envelope
        tiles_ok = (cfg.walk_tile <= self._STREAM_MAX_TILE
                    and cfg.link_tile <= self._STREAM_MAX_TILE)
        if self._rule_free(t, cfg):
            if self._table_bytes(t, self._PREFIX_FIELDS) <= budget:
                return "resident"
            return "streamed" if tiles_ok else None
        if not self._fuse_shapes_ok(cfg, seq_len):
            return None
        total = self._table_bytes(
            t, self._WALK_STREAM_FIELDS + self._WALK_RESIDENT_FIELDS)
        if total <= budget:
            return "resident"
        if tiles_ok and \
                self._table_bytes(t, self._WALK_RESIDENT_FIELDS) <= budget:
            return "streamed"
        return None

    def can_walk_batch(self, t, cfg, seq_len):
        return self.walk_variant(t, cfg, seq_len) is not None

    def walk_batch(self, t, cfg, qs, qlens):
        from repro.kernels import ops

        variant = self.walk_variant(t, cfg, int(qs.shape[1]))
        if variant is None:
            return super().walk_batch(t, cfg, qs, qlens)
        primitives.note_path("walk", f"pallas-{variant}")
        streamed = variant == "streamed"
        if pk.is_packed(t):
            return ops.locus_walk(t, cfg, qs, qlens, streamed=streamed)
        if self._rule_free(t, cfg):
            node, depth = ops.trie_walk(t.first_child, t.edge_char,
                                        t.edge_child, qs, qlens,
                                        streamed=streamed,
                                        walk_tile=cfg.walk_tile)
            B = int(qs.shape[0])
            hit = depth == qlens    # partial walks have no completions
            loci = jnp.full((B, cfg.frontier), NEG_ONE, jnp.int32)
            loci = loci.at[:, 0].set(jnp.where(hit, node, NEG_ONE))
            return loci, jnp.zeros((B,), jnp.int32)
        return ops.locus_walk(t, cfg, qs, qlens, streamed=streamed)

    def beam_variant(self, t: DeviceTrie, cfg: EngineConfig,
                     k: int) -> str | None:
        """Which native beam path serves this (trie, config, k):
        ``"resident"``, ``"streamed"``, or ``None`` (jnp fallback).

        The kernel requires the pool to hold the seed antichain (F <= W)
        and a pop no wider than the pool (P <= W) — both preconditions
        of the reference too — plus bounded selection-network widths;
        within that envelope the VMEM budget picks the tier."""
        if cfg.gens > self._BEAM_MAX_GENS \
                or cfg.expand > self._BEAM_MAX_EXPAND \
                or k > self._BEAM_MAX_K \
                or cfg.max_steps > self._BEAM_MAX_STEPS \
                or cfg.frontier > cfg.gens \
                or cfg.expand > cfg.gens:
            return None
        if pk.is_packed(t):
            # no streamed packed beam tier: the packed emission store is
            # already branch-count-sized, so over-budget cases are rare
            # and the jnp reference answers them bit-identically
            if self._table_bytes(t, self._BEAM_FIELDS_PACKED) \
                    <= self._budget(cfg):
                return "resident"
            return None
        if self._table_bytes(t, self._BEAM_FIELDS) <= self._budget(cfg):
            return "resident"
        # the streamed tier's emit-window scratch is [lanes, emit_tile]
        if cfg.emit_tile <= self._STREAM_MAX_TILE:
            return "streamed"
        return None

    def can_beam_batch(self, t, cfg, k):
        return self.beam_variant(t, cfg, k) is not None

    def beam_topk_batch(self, t, cfg, loci, k):
        variant = self.beam_variant(t, cfg, k)
        if variant is None:
            return super().beam_topk_batch(t, cfg, loci, k)
        from repro.kernels import ops

        primitives.note_path("beam", f"pallas-{variant}")
        return ops.beam_topk(t, cfg, loci, k,
                             streamed=variant == "streamed")

    def topk_with_payload(self, scores, payload, k):
        from repro.kernels import ops

        primitives.note_path("top-k", "pallas")
        return ops.topk_select(scores, payload, k)

    def cached_topk_batch(self, t, cfg, loci, k):
        assert cfg.use_cache and k <= cfg.cache_k, \
            "cache disabled or k too large"
        # the fused merge kernels hold the materialized (N, K) cache
        # tables whole in VMEM; there is no streamed cached tier yet
        # (ROADMAP follow-on), so caches over the budget answer through
        # the jnp reference merge instead of an unfittable kernel
        cache_fields = (self._CACHE_FIELDS_PACKED if pk.is_packed(t)
                        else self._CACHE_FIELDS)
        if self._table_bytes(t, cache_fields) > self._budget(cfg):
            return super().cached_topk_batch(t, cfg, loci, k)
        from repro.kernels import ops

        primitives.note_path("cached-merge", "pallas-resident")
        exact = jnp.ones(loci.shape[:-1], bool)
        if pk.is_packed(t):
            # quantized cache: translate loci to chain-representative
            # ranks and decode the row planes in-jit, then reuse the
            # uncompressed merge kernel unchanged
            s, p = ops.cached_topk_merge_packed(t, loci, k)
            return s, p, exact
        if self._rule_free(t, cfg):
            # single-locus rows: the gather is one row per query; merging
            # reduces to selecting from the node's own (sorted) top-K list
            sc, si = cached.gather_cached(t, loci[:, :1])
            s, p = self.topk_with_payload(sc, si, k)
            return s, p, exact
        s, p = ops.cached_topk_merge(loci, t.topk_score, t.topk_sid, k)
        return s, p, exact


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_SUBSTRATES: dict[str, Substrate] = {}


def register_substrate(name: str, substrate: Substrate) -> Substrate:
    """Register an execution substrate; a new backend is an additive
    ``register_substrate("<name>", MySubstrate())`` away."""
    if name in _SUBSTRATES:
        raise ValueError(f"substrate {name!r} already registered")
    _SUBSTRATES[name] = substrate
    return substrate


def get_substrate(name: str) -> Substrate:
    try:
        return _SUBSTRATES[name]
    except KeyError:
        raise ValueError(
            f"unknown substrate {name!r}; registered: "
            f"{available_substrates()}") from None


def available_substrates() -> list[str]:
    return sorted(_SUBSTRATES)


def resolve_substrate(name: str) -> str:
    """Resolve a user-facing substrate choice to a registry name.

    ``"auto"`` picks ``jnp`` on every backend: it is the only substrate
    whose whole path compiles for a TPU.  The Pallas kernels still read
    their tables with per-lane gathers (and the locus DP compacts with a
    scatter), which the TPU's Mosaic compiler does not lower;
    ``tests/test_tpu_compile.py`` holds each refusal as a strict xfail.
    So ``"pallas"`` is opt-in by name: on a TPU it raises the compiler's
    own error, elsewhere it runs the kernels in interpret mode.
    Concrete names are validated against the registry and passed
    through.
    """
    if name == "auto":
        return "jnp"
    get_substrate(name)
    return name


register_substrate("jnp", Substrate())
register_substrate("pallas", PallasSubstrate())


# ---------------------------------------------------------------------------
# public entry points (substrate-dispatched)
# ---------------------------------------------------------------------------


def _phase2_batch(t, cfg, loci, k, sub):
    """Phase-2 dispatch: cached merge when materialized and k fits, else
    beam."""
    if cfg.use_cache and k <= cfg.cache_k:
        return sub.cached_topk_batch(t, cfg, loci, k)
    return sub.beam_topk_batch(t, cfg, loci, k)


def topk_phase2(t: DeviceTrie, cfg: EngineConfig, loci: jax.Array, k: int,
                sub=None):
    """Single-row phase 2 (loci [F]); used by the incremental session."""
    sub = primitives.resolve_sub(cfg, sub)
    s, p, e = _phase2_batch(t, cfg, loci[None], k, sub)
    return s[0], p[0], e[0]


def topk_phase2_batch(t: DeviceTrie, cfg: EngineConfig, loci: jax.Array,
                      k: int, sub=None):
    """Batched phase 2 (loci [B, F]) -> (scores[B,k], sids[B,k], exact[B]);
    one dispatch for a whole coalesced micro-batch block."""
    sub = primitives.resolve_sub(cfg, sub)
    return _phase2_batch(t, cfg, loci, k, sub)


def complete_batch(t: DeviceTrie, cfg: EngineConfig, qs: jax.Array,
                   qlens: jax.Array, k: int, sub=None):
    """qs: int32[B, L]; qlens: int32[B] -> (scores[B,k], sids[B,k],
    exact[B])."""
    sub = primitives.resolve_sub(cfg, sub)
    with jax.named_scope("engine.walk"):
        loci, overflow = sub.walk_batch(t, cfg, qs, qlens)
    with jax.named_scope("engine.topk"):
        scores, sids, exact = _phase2_batch(t, cfg, loci, k, sub)
        return scores, sids, exact & (overflow == 0)


def complete_one(t: DeviceTrie, cfg: EngineConfig, q: jax.Array,
                 qlen: jax.Array, k: int, sub=None):
    scores, sids, exact = complete_batch(
        t, cfg, q[None], jnp.asarray(qlen)[None], k, sub)
    return scores[0], sids[0], exact[0]
