"""CompletionIndex: the queryable, persistable, *mutable* completion index.

Construction lives in :mod:`repro.api.build` (driven by an
:class:`~repro.api.spec.IndexSpec`); this module owns the device arrays,
the bounded compile cache, batched lookup with the exactness-retry guard,
persistence, the session entry points, and the online-mutation surface:

- ``insert``/``delete``/``update_score`` absorb changes into a
  :class:`~repro.core.engine.overlay.DeltaOverlay` (tombstones + a small
  side-index) merged into results at top-k time — no rebuild per change;
- ``compact()`` folds the overlay into a freshly built index and
  hot-swaps it in place.  The index is *epoch-versioned*: every swap (and
  every :meth:`reconfigure`) bumps ``epoch``, and live sessions /
  scheduler slabs migrate onto the new epoch at their next keystroke
  boundary by replaying their retained prefixes;
- ``reconfigure(...)`` is the single runtime-knob entry point (substrate,
  memory budget, engine widths), revalidating through ``IndexSpec``.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.build import BuildStats, build_index
from repro.api.compile_cache import CompileCache, bucket_size
from repro.api.spec import IndexSpec
from repro.core import engine as eng
from repro.core import trie_build as tb
from repro.core.alphabet import pad_queries
from repro.spans import RECORDER


def _to_device(trie: tb.DictTrie, rule_trie: tb.RuleTrie) -> eng.DeviceTrie:
    j = jnp.asarray
    has_cache = trie.topk_score is not None
    dummy = np.full((1, 1), -1, np.int32)
    if trie.has_packed:
        # compressed layout: only the packed side tables (their narrow
        # dtypes preserved), the kept link store, and the rule trie go to
        # the device — the dense planes ship as 0-size dummies so the
        # NamedTuple stays a uniform pytree while costing nothing
        z1 = jnp.zeros((0,), jnp.int32)
        z2 = jnp.zeros((0, 1), jnp.int32)
        packed_kw = {f: j(getattr(trie, f)) for f in tb.PACKED_ONLY_FIELDS
                     if getattr(trie, f) is not None}
        return eng.DeviceTrie(
            depth=z1, max_score=z1, leaf_score=z1, leaf_sid=z1,
            syn_mask=jnp.zeros((0,), bool), tout=z1,
            first_child=z1, edge_char=z1, edge_child=z1,
            s_first_child=z1, s_edge_char=z1, s_edge_child=z1,
            emit_ptr=z1, emit_node=z1, emit_score=z1, emit_is_leaf=z1,
            tele_plane=z2, link_ptr=z1,
            link_rule=j(trie.link_rule), link_target=j(trie.link_target),
            r_first_child=j(rule_trie.first_child),
            r_edge_char=j(rule_trie.edge_char),
            r_edge_child=j(rule_trie.edge_child),
            r_term_plane=j(rule_trie.term_plane),
            r_rule_len=j(rule_trie.rule_len),
            topk_score=j(dummy), topk_sid=j(dummy),
            **packed_kw,
        )
    if trie.tele_plane is None or trie.link_ptr is None \
            or rule_trie.term_plane is None:
        tb.pack_rule_planes(trie, rule_trie)
    return eng.DeviceTrie(
        depth=j(trie.depth), max_score=j(trie.max_score),
        leaf_score=j(trie.leaf_score), leaf_sid=j(trie.leaf_sid),
        syn_mask=j(trie.syn_mask), tout=j(trie.tout),
        first_child=j(trie.first_child), edge_char=j(trie.edge_char),
        edge_child=j(trie.edge_child),
        s_first_child=j(trie.s_first_child), s_edge_char=j(trie.s_edge_char),
        s_edge_child=j(trie.s_edge_child),
        emit_ptr=j(trie.emit_ptr), emit_node=j(trie.emit_node),
        emit_score=j(trie.emit_score), emit_is_leaf=j(trie.emit_is_leaf),
        tele_plane=j(trie.tele_plane),
        link_ptr=j(trie.link_ptr), link_rule=j(trie.link_rule),
        link_target=j(trie.link_target),
        r_first_child=j(rule_trie.first_child), r_edge_char=j(rule_trie.edge_char),
        r_edge_child=j(rule_trie.edge_child),
        r_term_plane=j(rule_trie.term_plane), r_rule_len=j(rule_trie.rule_len),
        topk_score=j(trie.topk_score if has_cache else dummy),
        topk_sid=j(trie.topk_sid if has_cache else dummy),
    )


#: IndexSpec fields :meth:`CompletionIndex.reconfigure` may change at
#: runtime — they ride ``EngineConfig`` (and thus every compile-cache
#: key), so flipping them never touches the built structures.
RUNTIME_FIELDS = ("substrate", "memory_budget", "frontier", "gens",
                  "expand", "max_steps", "edit_budget")
#: fields baked into the built structures at construction time; changing
#: them means a rebuild (``build_index`` or the next ``compact()``).
BUILD_FIELDS = ("kind", "alpha", "cache_k", "compression", "multiterm_gap")


@dataclass
class LookupStats:
    """Counts of the one-shot lookup path (``_complete_base``), kept for
    the life of the index; a reader takes the difference of two
    readings.  ``calls`` numbers the calls to :meth:`complete` (the
    request id of its spans)."""

    calls: int = 0
    queries: int = 0         # rows looked up, padding left out
    retry_rounds: int = 0    # widened re-dispatches for inexact rows
    retried_rows: int = 0    # rows those rounds carried

    @property
    def retries_per_1k(self) -> float:
        return 1000.0 * self.retry_rounds / max(self.queries, 1)


@dataclass
class PreparedCompaction:
    """A compaction ready to hot-swap: the freshly built index plus the
    {string: score} snapshot it was built from (mutations landing after
    the snapshot are re-applied as a new overlay at apply time)."""

    index: "CompletionIndex"
    snapshot: dict


class CompletionIndex:
    """A synonym-aware top-k completion index (TT, ET, HT or plain)."""

    def __init__(self, spec: IndexSpec, trie, rule_trie, rules, strings,
                 scores, cfg: eng.EngineConfig, stats: BuildStats,
                 compile_cache_size: int = 32, epoch: int = 0):
        self.spec = spec
        self.trie = trie
        self.rule_trie = rule_trie
        self.rules = rules
        self.strings = strings          # sorted; leaf_sid indexes this
        self.scores = scores
        self.cfg = cfg
        self.stats = stats
        self.device = _to_device(trie, rule_trie)
        self._compile_cache = CompileCache(maxsize=compile_cache_size)
        #: bumped by every hot-swap (compact) and reconfigure; sessions
        #: and scheduler slabs compare against it to know when to migrate
        self.epoch = epoch
        self._overlay: eng.DeltaOverlay | None = None
        self.lookups = LookupStats()

    @property
    def kind(self) -> str:
        return self.spec.kind

    @property
    def substrate(self) -> str:
        """The resolved execution substrate lookups run on."""
        return self.cfg.substrate

    @property
    def compression(self) -> str:
        """On-device layout: "none" (full-width) or "packed" (format v4)."""
        return self.cfg.compression

    @property
    def memory_budget(self) -> int:
        """VMEM byte budget for table residency (0 = substrate default)."""
        return self.cfg.memory_budget

    # -- runtime reconfiguration -------------------------------------------

    def reconfigure(self, **changes) -> "CompletionIndex":
        """Change runtime knobs in one validated step; returns ``self``.

        Accepts the :data:`RUNTIME_FIELDS` subset of ``IndexSpec``
        (``substrate``, ``memory_budget``, ``frontier``, ``gens``,
        ``expand``, ``max_steps``, ``edit_budget``), revalidates the
        resulting spec like a
        build would, and folds the changes into ``EngineConfig`` — which
        keys every jit/compile-cache entry, so stale executables can
        never be hit while ones for the old configuration stay cached.
        Any actual change bumps :attr:`epoch`: compiled sessions are
        bound to the old config and re-derive their state at the next
        keystroke boundary, exactly like a hot-swap.

        Build-time fields (:data:`BUILD_FIELDS`) are rejected — rebuild
        via ``build_index`` or fold them into the next :meth:`compact`.
        """
        build_time = set(changes) & set(BUILD_FIELDS)
        if build_time:
            raise ValueError(
                f"{sorted(build_time)} are build-time fields baked into "
                f"the index structures; rebuild with build_index(...) or "
                f"fold the change into the next compact()")
        unknown = set(changes) - set(RUNTIME_FIELDS)
        if unknown:
            raise ValueError(
                f"unknown reconfigure field(s) {sorted(unknown)}; "
                f"runtime knobs are {RUNTIME_FIELDS}")
        changes = {k: v for k, v in changes.items()
                   if getattr(self.spec, k) != v}
        if not changes:
            return self
        spec = self.spec.replace(**changes).validate()
        cfg_kw = dict(changes)
        if "substrate" in cfg_kw:
            cfg_kw["substrate"] = eng.resolve_substrate(cfg_kw["substrate"])
        self.spec = spec
        self.cfg = replace(self.cfg, **cfg_kw)
        self.epoch += 1
        return self

    def set_substrate(self, name: str) -> "CompletionIndex":
        """Deprecated alias of ``reconfigure(substrate=...)``."""
        warnings.warn(
            "CompletionIndex.set_substrate() is deprecated; use "
            "reconfigure(substrate=...)", DeprecationWarning, stacklevel=2)
        return self.reconfigure(substrate=name)

    def set_memory_budget(self, n: int) -> "CompletionIndex":
        """Deprecated alias of ``reconfigure(memory_budget=...)``."""
        warnings.warn(
            "CompletionIndex.set_memory_budget() is deprecated; use "
            "reconfigure(memory_budget=...)", DeprecationWarning,
            stacklevel=2)
        return self.reconfigure(memory_budget=n)

    # -- construction ------------------------------------------------------

    @staticmethod
    def build(strings, scores, rules, kind: str = "et", *,
              alpha: float = 0.5, cache_k: int = 0,
              frontier: int = 32, gens: int = 48, expand: int = 8,
              max_steps: int = 512, compression: str = "none",
              edit_budget: int = 0,
              multiterm_gap: int = 2) -> "CompletionIndex":
        """Back-compat keyword constructor; equivalent to
        ``build_index(strings, scores, rules, IndexSpec(...))``."""
        spec = IndexSpec(kind=kind, alpha=alpha, cache_k=cache_k,
                         frontier=frontier, gens=gens, expand=expand,
                         max_steps=max_steps, compression=compression,
                         edit_budget=edit_budget,
                         multiterm_gap=multiterm_gap)
        return build_index(strings, scores, rules, spec)

    @staticmethod
    def from_spec(strings, scores, rules,
                  spec: IndexSpec) -> "CompletionIndex":
        return build_index(strings, scores, rules, spec)

    # -- persistence -------------------------------------------------------

    def save(self, path: str) -> None:
        """Write a versioned npz container; ``CompletionIndex.load(path)``
        restores it without re-running trie construction.

        The container holds only the built base structures, so saving
        with uncompacted mutations would silently drop them — fold them
        first (``compact()``, or ``compact(handoff_path=...)`` to write
        the folded container in the same step)."""
        if self.has_mutations:
            raise ValueError(
                "index has uncompacted mutations that save() would drop; "
                "call compact() first — compact(handoff_path=path) writes "
                "the folded container as part of the swap")
        from repro.api.persist import save_index
        save_index(self, path)

    @classmethod
    def load(cls, path: str) -> "CompletionIndex":
        from repro.api.persist import load_index_parts
        p = load_index_parts(path)
        return cls(p["spec"], p["trie"], p["rule_trie"], p["rules"],
                   p["strings"], p["scores"], p["cfg"], p["stats"],
                   epoch=p["epoch"])

    # -- mutations (delta overlay) -----------------------------------------

    @staticmethod
    def _as_key(string) -> bytes:
        b = string.encode() if isinstance(string, str) else bytes(string)
        if not b:
            raise ValueError("cannot mutate the empty string")
        return b

    def _overlay_mut(self) -> eng.DeltaOverlay:
        if self._overlay is None:
            self._overlay = eng.DeltaOverlay()
        return self._overlay

    @property
    def has_mutations(self) -> bool:
        """True while the overlay holds uncompacted mutations; queries
        route through the merged path and sessions fall back to the
        one-shot lookup until :meth:`compact` folds them away."""
        ov = self._overlay
        return ov is not None and ov.active

    @property
    def mutation_backlog(self) -> int:
        """Pending overlay entries (inserts/re-scores + tombstones) — the
        serving loop's compaction trigger."""
        ov = self._overlay
        return 0 if ov is None else len(ov.added) + len(ov.tombstones)

    def insert(self, string, score: int) -> "CompletionIndex":
        """Insert (upsert: re-score if already present) without rebuild."""
        if score < 0:
            raise ValueError("scores are non-negative")
        self._overlay_mut().upsert(self.strings, self._as_key(string),
                                   int(score))
        return self

    def delete(self, string) -> "CompletionIndex":
        """Delete a live string; raises KeyError when it is not live."""
        self._overlay_mut().remove(self.strings, self._as_key(string))
        return self

    def update_score(self, string, score: int) -> "CompletionIndex":
        """Re-score a live string; raises KeyError when it is not live
        (unlike the upserting :meth:`insert`)."""
        b = self._as_key(string)
        ov = self._overlay
        live = (ov.is_live(self.strings, b) if ov is not None
                else eng.DeltaOverlay._base_sid(self.strings, b) >= 0)
        if not live:
            raise KeyError(f"{b!r} is not in the index; use insert()")
        return self.insert(b, score)

    @property
    def live_strings(self) -> list:
        """The current dictionary (base − deletions + inserts), sorted;
        merged-path sids index this list exactly as base sids index
        :attr:`strings`."""
        if not self.has_mutations:
            return self.strings
        self._overlay.refresh(self)
        return self._overlay.live

    def live_items(self) -> dict:
        """{string: score} of the current dictionary contents."""
        live = {s: int(r) for s, r in zip(
            self.strings, np.asarray(self.scores).tolist())}
        ov = self._overlay
        if ov is not None:
            for s in ov.tombstones:
                live.pop(s, None)
            live.update(ov.added)
        return live

    # -- compaction / hot-swap ---------------------------------------------

    def prepare_compaction(self) -> PreparedCompaction:
        """Fold the current contents into a freshly built index.

        The expensive half of a compaction, safe to run off-thread: it
        reads one consistent snapshot and mutates nothing; the cheap
        :meth:`apply_compaction` swaps it in at a convenient boundary."""
        snapshot = self.live_items()
        strings = sorted(snapshot)
        scores = [snapshot[s] for s in strings]
        fresh = build_index(strings, scores, self.rules, self.spec)
        return PreparedCompaction(index=fresh, snapshot=snapshot)

    def apply_compaction(
            self, prepared: PreparedCompaction) -> "CompletionIndex":
        """Hot-swap a prepared compaction in place (cheap, synchronous).

        Adopts the fresh structures, drops the compile cache — the fresh
        tables have new shapes, so the old executables would never be
        hit again — and bumps :attr:`epoch` so live sessions re-derive
        their state at the next keystroke boundary.  Mutations
        that landed after the snapshot survive: they are diffed against
        it and re-applied as a new overlay on the fresh base."""
        current = self.live_items()
        fresh = prepared.index
        desired_spec = self.spec
        self.spec = fresh.spec
        self.trie, self.rule_trie = fresh.trie, fresh.rule_trie
        self.rules = fresh.rules
        self.strings, self.scores = fresh.strings, fresh.scores
        self.cfg, self.stats = fresh.cfg, fresh.stats
        self.device = fresh.device
        self._compile_cache = CompileCache(
            maxsize=self._compile_cache.maxsize)
        self._overlay = None
        self.epoch += 1
        snap = prepared.snapshot
        for s, sc in current.items():
            if snap.get(s) != sc:
                self.insert(s, sc)
        for s in snap:
            if s not in current:
                self.delete(s)
        if desired_spec != self.spec:
            # a reconfigure() raced the prepare; re-apply its runtime
            # knobs on top of the adopted spec (build-time fields cannot
            # diverge — reconfigure rejects them)
            runtime = {f: getattr(desired_spec, f) for f in RUNTIME_FIELDS
                       if getattr(desired_spec, f) != getattr(self.spec, f)}
            if runtime:
                self.reconfigure(**runtime)
        return self

    def compact(self, handoff_path: str | None = None) -> "CompletionIndex":
        """Fold the overlay into a fresh index and hot-swap it in place.

        ``handoff_path`` routes the swap through the versioned npz
        container (save + load) — the restart-without-downtime shape: the
        folded index lands on disk as a side effect, and what is swapped
        in is bit-for-bit what a restarting process would load."""
        prepared = self.prepare_compaction()
        if handoff_path is not None:
            prepared.index.save(handoff_path)
            prepared = PreparedCompaction(
                index=CompletionIndex.load(handoff_path),
                snapshot=prepared.snapshot)
        return self.apply_compaction(prepared)

    # -- lookup ------------------------------------------------------------

    # Every jitted entry point takes the DeviceTrie as its first argument
    # and the getters bind the current tables with ``partial``: a closure
    # over the tables would embed them in each program as HLO constants
    # (gigabytes at a million strings) and key the compile cache on the
    # data.  The cached executables depend only on shapes and ``cfg``.

    def _fn(self, batch: int, length: int, k: int, cfg: eng.EngineConfig):
        key = ("batch", batch, length, k, cfg)

        def factory():
            def complete_batch(dev, qs, qlens):
                return eng.complete_batch(dev, cfg, qs, qlens, k)

            return jax.jit(complete_batch)

        return partial(self._compile_cache.get(key, factory), self.device)

    def _session_fns(self, k: int):
        """(init, advance-one-char, topk) jitted for this index's cfg."""
        key = ("session", k, self.cfg)

        def factory():
            cfg = self.cfg

            def session_init(dev):
                return eng.init_locus_state(dev, cfg)

            def session_advance(dev, state, c):
                return eng.advance_locus_state(dev, cfg, state, c)

            def session_topk(dev, state):
                return eng.topk_from_loci(dev, cfg, state, k)

            return tuple(map(jax.jit, (session_init, session_advance,
                                       session_topk)))

        return tuple(partial(f, self.device)
                     for f in self._compile_cache.get(key, factory))

    def _slab_fns(self, block: int):
        """(init, advance) jitted at the fixed ``[block]`` lane shape.

        The continuous-batching scheduler's hot pair: ``init()`` builds a
        stacked empty-prefix LocusState slab, ``advance(slab, chars,
        resets)`` re-initializes lanes flagged in ``resets`` and then
        advances every lane whose char is >= 0, all in one dispatch.
        """
        key = ("slab", block, self.cfg)

        def factory():
            cfg = self.cfg

            def slab_init(dev):
                return eng.init_locus_batch(dev, cfg, block)

            def slab_advance(dev, slab, chars, resets):
                return eng.advance_loci_batch(dev, cfg, slab, chars,
                                              resets=resets)

            # the slab is threaded flush-to-flush and never read after the
            # advance, so donating it lets XLA update lanes in place
            return jax.jit(slab_init), jax.jit(slab_advance,
                                               donate_argnums=1)

        return tuple(partial(f, self.device)
                     for f in self._compile_cache.get(key, factory))

    def _slab_topk_fn(self, block: int, k: int):
        """Batched top-k over a state slab, jitted per (block, k)."""
        key = ("slab_topk", block, k, self.cfg)

        def factory():
            cfg = self.cfg

            def slab_topk(dev, slab):
                return eng.topk_from_loci_batch(dev, cfg, slab, k)

            return jax.jit(slab_topk)

        return partial(self._compile_cache.get(key, factory), self.device)

    def compiled_programs(self) -> list[tuple[tuple, str]]:
        """``(cache key, compiled HLO text)`` of every program in the
        compile cache, compiled again from the key's shapes (where JAX's
        persistent cache is on, it returns the executable that ran).

        A device trace names each operation by its HLO instruction; the
        text's ``op_name`` metadata carries the engine phase the
        instruction belongs to (``engine.walk``, ``engine.advance``,
        ``engine.topk``)."""
        def spec(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype)

        def slab_spec(block):
            return jax.eval_shape(partial(eng.init_locus_batch, cfg=self.cfg,
                                          batch=block), self.device)

        out = []
        for key in self._compile_cache.keys():
            kind, cfg = key[0], key[-1]
            if kind == "batch":
                _, b, n, k, _ = key
                progs = [(self._fn(b, n, k, cfg), (spec((b, n)), spec((b,))))]
            elif cfg != self.cfg:
                continue        # compiled for an earlier configuration
            elif kind == "slab":
                block = key[1]
                init, adv = self._slab_fns(block)
                progs = [(init, ()),
                         (adv, (slab_spec(block), spec((block,)),
                                spec((block,), bool)))]
            elif kind == "slab_topk":
                progs = [(self._slab_topk_fn(key[1], key[2]),
                          (slab_spec(key[1]),))]
            elif kind == "session":
                init, adv, topk = self._session_fns(key[1])
                state = jax.eval_shape(init)
                progs = [(init, ()), (adv, (state, spec(()))),
                         (topk, (state,))]
            else:               # overlay_merge: (B, C, k)
                _, b, c, k, _ = key
                progs = [(partial(self._merge_fn(b, c, k)),
                          (spec((b, c)), spec((b, c))))]
            for fn, args in progs:
                text = fn.func.lower(*fn.args, *args).compile().as_text()
                out.append((key, text))
        return out

    def session(self, k: int = 10):
        """Open a stateful incremental-typing session (see
        :class:`repro.api.session.Session`)."""
        from repro.api.session import Session
        return Session(self, k=k)

    def complete_batch_padded(self, qs: np.ndarray, qlens: np.ndarray,
                              k: int):
        """Device entry point: qs int32[B, L] (-1 padded). Shapes are
        bucketed to powers of two before jit so drifting batch sizes share
        executables. Retries inexact queries with widened search (exactness
        guard of §2.2).

        With pending mutations the answer is the overlay-merged one
        (:meth:`_complete_mutated`) and the returned sids index
        :attr:`live_strings`; otherwise they index :attr:`strings` — the
        two coincide exactly when :attr:`has_mutations` is False."""
        if self.has_mutations:
            return self._complete_mutated(qs, qlens, k)
        return self._complete_base(qs, qlens, k)

    def _complete_base(self, qs: np.ndarray, qlens: np.ndarray, k: int):
        rec = RECORDER
        rid = self.lookups.calls
        B, L = qs.shape
        self.lookups.queries += B
        Bb, Lb = bucket_size(B, minimum=1), bucket_size(L)
        if (Bb, Lb) != (B, L):
            sp = rec.begin("index.pad", rid) if rec.on else -1
            qs = np.pad(qs, ((0, Bb - B), (0, Lb - L)), constant_values=-1)
            qlens = np.pad(qlens, (0, Bb - B))
            if sp >= 0:
                rec.end(sp)
        cfg = self.cfg
        fn = self._fn(Bb, Lb, k, cfg)
        out = fn(qs, qlens)
        sp = rec.begin("index.fetch", rid) if rec.on else -1
        scores, sids, exact = jax.tree.map(np.asarray, out)
        if sp >= 0:
            rec.end(sp)
        bad = ~exact
        bad[B:] = False
        if bad.any():   # np.asarray views of jit output are read-only
            scores, sids = scores.copy(), sids.copy()
        tries = 0
        while bad.any() and tries < 3:
            sp = rec.begin("index.retry", rid) if rec.on else -1
            # the widened config re-dispatches through the substrate, so
            # each retry round re-probes can_beam_batch: the first round
            # (gens x4) re-enters the fused beam kernel at the default
            # widths; rounds that outgrow its envelope fall back to the
            # jnp reference with identical results
            cfg = replace(cfg, frontier=cfg.frontier * 2, gens=cfg.gens * 4,
                          max_steps=cfg.max_steps * 4, use_cache=False)
            sub = np.nonzero(bad)[0]
            self.lookups.retry_rounds += 1
            self.lookups.retried_rows += len(sub)
            Sb = bucket_size(len(sub), minimum=1)
            pad_sub = np.pad(sub, (0, Sb - len(sub)))  # repeat row 0: harmless
            fn2 = self._fn(Sb, Lb, k, cfg)
            out = fn2(qs[pad_sub], qlens[pad_sub])
            sf = rec.begin("index.fetch", rid) if sp >= 0 else -1
            s2, i2, e2 = jax.tree.map(np.asarray, out)
            if sf >= 0:
                rec.end(sf)
            scores[sub], sids[sub] = s2[:len(sub)], i2[:len(sub)]
            bad2 = np.zeros_like(bad)
            bad2[sub] = ~e2[:len(sub)]
            bad = bad2
            tries += 1
            if sp >= 0:
                rec.end(sp)
        return scores[:B], sids[:B]

    def _merge_fn(self, B: int, C: int, k: int):
        """Jitted overlay merge (sort-by-grank + substrate top-k), cached
        per candidate shape like every other compiled entry point."""
        key = ("overlay_merge", B, C, k, self.cfg)

        def factory():
            sub = eng.get_substrate(self.cfg.substrate)

            def overlay_merge(s, g):
                return eng.merge_overlay_topk(s, g, k, sub)

            return jax.jit(overlay_merge)

        return self._compile_cache.get(key, factory)

    def _complete_mutated(self, qs: np.ndarray, qlens: np.ndarray, k: int):
        """Merged lookup under pending mutations.

        Base is over-fetched to k + D' (D' = tombstone count bucketed to
        a power of two, so a growing backlog reuses executables) — every
        result row can lose at most every tombstone — then tombstoned
        hits are masked out host-side and both candidate sets are
        relabeled to *global ranks* (their sid in a from-scratch rebuild;
        see :mod:`repro.core.engine.overlay`).  One substrate-routed
        fused selection returns the top-k bit-identical to that rebuild,
        and the grank "sids" decode against :attr:`live_strings`."""
        ov = self._overlay
        ov.refresh(self)
        n_dead = int(ov.base_dead.sum())
        k_base = k + (bucket_size(n_dead, minimum=1) if n_dead else 0)
        b_scores, b_sids = self._complete_base(qs, qlens, k_base)
        valid = b_sids >= 0
        sid0 = np.where(valid, b_sids, 0)
        keep = valid & ~ov.base_dead[sid0]
        cand_s = np.where(keep, b_scores, -1).astype(np.int32)
        cand_g = np.where(keep, ov.base_grank[sid0],
                          eng.INT_MAX).astype(np.int32)
        if ov.index is not None:
            o_scores, o_sids = ov.index.complete_batch_padded(qs, qlens, k)
            o_valid = o_sids >= 0
            o_sid0 = np.where(o_valid, o_sids, 0)
            cand_s = np.concatenate(
                [cand_s, np.where(o_valid, o_scores, -1).astype(np.int32)],
                axis=1)
            cand_g = np.concatenate(
                [cand_g, np.where(o_valid, ov.ov_grank[o_sid0],
                                  eng.INT_MAX).astype(np.int32)], axis=1)
        fn = self._merge_fn(cand_s.shape[0], cand_s.shape[1], k)
        scores, granks = jax.tree.map(np.asarray, fn(cand_s, cand_g))
        return scores, granks

    def complete(self, queries: list[str | bytes], k: int = 10):
        """Top-k completions for a batch of query strings.

        Returns a list (per query) of (score, suggestion string) pairs.
        """
        rec = RECORDER
        self.lookups.calls += 1
        rid = self.lookups.calls
        sc = rec.begin("index.complete", rid) if rec.on else -1
        sp = rec.begin("index.pad", rid) if sc >= 0 else -1
        max_len = max((len(q.encode() if isinstance(q, str) else q)
                       for q in queries), default=1)
        qs, qlens = pad_queries(queries, max(max_len, 1))
        if sp >= 0:
            rec.end(sp)
        scores, sids = self.complete_batch_padded(qs, qlens, k)
        sp = rec.begin("index.decode", rid) if sc >= 0 else -1
        out = []
        for b in range(len(queries)):
            out.append(self._decode_row(scores[b], sids[b]))
        if sp >= 0:
            rec.end(sp)
            rec.end(sc)
        return out

    def _decode_row(self, scores, sids) -> list[tuple[int, str]]:
        # tolist() converts the row in one C pass: the per-keystroke
        # serving paths decode thousands of these, and looping numpy
        # scalars costs more than the decode itself
        row = []
        strings = self.live_strings
        for score, sid in zip(np.asarray(scores).tolist(),
                              np.asarray(sids).tolist()):
            if score < 0 or sid < 0:
                continue
            row.append((score, strings[sid].decode(
                "utf-8", errors="replace")))
        return row
