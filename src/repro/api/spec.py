"""Declarative index specification + pluggable builder registry.

An :class:`IndexSpec` captures *what* index to build (kind, HT space budget,
cache depth) and the static engine widths that become the jit shape key —
replacing the keyword soup of the old ``CompletionIndex.build(...)``.  The
per-kind rule-partitioning policies (``tt`` / ``et`` / ``ht`` / ``plain``)
register themselves in a builder registry, so a new index kind is an
additive ``@register_builder("<kind>")`` away.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable

import numpy as np

from repro.core import trie_build as tb


@dataclass(frozen=True)
class IndexSpec:
    """Everything needed to (re)build a completion index, minus the data.

    kind: "tt" (twin tries), "et" (expansion trie), "ht" (hybrid), or
        "plain" (prefix-only, no synonym support) — or any kind added to
        the registry via :func:`register_builder`.
    alpha: HT space ratio in [0, 1] (paper Fig. 8); ignored by other kinds.
    cache_k: materialize per-node top-K lists (0 = off; beyond-paper).
    frontier/gens/expand/max_steps: static engine widths (jit shape key).
    substrate: execution substrate — "jnp" (reference), "pallas" (tuned
        kernels; interpret mode off-TPU, not yet compilable for a TPU),
        or "auto" (jnp on every backend, see
        :func:`~repro.core.engine.substrate.resolve_substrate`).
        Resolved at build/load time against the substrate registry in
        :mod:`repro.core.engine.substrate`.
    memory_budget: VMEM bytes the pallas substrate may spend keeping
        tables resident; tries whose tables exceed it run the
        DMA-streamed kernel tier (HBM-resident tables) instead of
        falling back to jnp.  0 = substrate default.
    compression: on-device table layout — "none" keeps the uniform-i32
        arrays; "packed" builds the compressed layout
        (:func:`repro.core.trie_build.pack_compressed`): narrow dtype
        tiers, chain-collapsed unary paths, elided empty planes, and a
        quantized top-K cache.  Bit-identical results, ~an order of
        magnitude fewer bytes/string; persisted as format v4.
    """

    kind: str = "et"
    alpha: float = 0.5
    cache_k: int = 0
    frontier: int = 32
    gens: int = 48
    expand: int = 8
    max_steps: int = 512
    substrate: str = "auto"
    memory_budget: int = 0
    compression: str = "none"
    # bounded-edit (typo-tolerant) matching: up to edit_budget
    # substitutions/insertions/deletions may be spent on the literal
    # characters of a query (rule lhs and synonym-variant characters must
    # still be typed exactly).  Static — joins EngineConfig and every
    # compile-cache key; runtime-reconfigurable via
    # ``CompletionIndex.reconfigure(edit_budget=...)``.  0 = exact.
    edit_budget: int = 0
    # multi-term mode (kind="multiterm"): max token-gap bridged by the
    # synthesized skip rules — typing a space may skip up to this many
    # dictionary tokens, so the last token completes conditioned on an
    # earlier-token context.  Ignored by other kinds.
    multiterm_gap: int = 2

    def validate(self) -> "IndexSpec":
        if self.kind not in _BUILDERS:
            raise ValueError(
                f"unknown index kind {self.kind!r}; registered kinds: "
                f"{registered_kinds()}")
        if not 0.0 <= self.alpha <= 1.0:
            raise ValueError(f"alpha must be in [0, 1], got {self.alpha}")
        from repro.core.engine.substrate import available_substrates
        if self.substrate != "auto" and \
                self.substrate not in available_substrates():
            raise ValueError(
                f"unknown substrate {self.substrate!r}; expected 'auto' or "
                f"one of {available_substrates()}")
        if self.compression not in ("none", "packed"):
            raise ValueError(
                f"unknown compression {self.compression!r}; expected "
                "'none' or 'packed'")
        for name in ("cache_k", "memory_budget"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0")
        for name in ("frontier", "gens", "expand", "max_steps",
                     "multiterm_gap"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{name} must be positive")
        if not 0 <= self.edit_budget <= 2:
            raise ValueError(
                f"edit_budget must be in [0, 2], got {self.edit_budget}")
        return self

    def validate_sharded(self) -> "IndexSpec":
        """Validate for use across ``ShardedCompletionIndex`` shards.

        The packed (format v4) layout cannot be stacked: shard stacking
        pads every table to the widest shard, which breaks the packed
        side tables' sorted-rank invariants.  Rejecting the spec here
        surfaces the problem at construction time with the workaround,
        instead of a ``NotImplementedError`` deep in ``stack_shards``."""
        self.validate()
        if self.compression != "none":
            raise ValueError(
                f"compression={self.compression!r} is unsupported on "
                f"sharded indexes: stacking pads the packed side tables "
                f"and breaks their sorted-rank invariants. Build shards "
                f"with compression='none'; to keep large shards off VMEM "
                f"set memory_budget so they run the DMA-streamed tier")
        return self

    def replace(self, **kw) -> "IndexSpec":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "IndexSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known}).validate()


@dataclass
class BuildContext:
    """What a kind-specific builder sees: the pure dictionary trie plus the
    full link candidate set (anchor, rule, target) found on it."""

    spec: IndexSpec
    trie: tb.DictTrie
    rules: list[tb.SynonymRule]
    anchors: np.ndarray  # int32[L]
    rids: np.ndarray     # int32[L]
    targets: np.ndarray  # int32[L]


# A builder decides, per rule, whether it is expanded into synonym branches
# (ET side) and/or kept in the link store (TT side).
Builder = Callable[[BuildContext], tuple[np.ndarray, np.ndarray]]

_BUILDERS: dict[str, Builder] = {}


def register_builder(kind: str):
    """Register a rule-partitioning policy for an index kind.

    The decorated function maps a :class:`BuildContext` to boolean masks
    ``(expand_mask[R], keep_links[R])`` over rule ids.
    """

    def deco(fn: Builder) -> Builder:
        if kind in _BUILDERS:
            raise ValueError(f"index kind {kind!r} already registered")
        _BUILDERS[kind] = fn
        return fn

    return deco


# Optional per-kind rule synthesizers: run before link finding, they map
# (spec, strings, user rules) to extra SynonymRules the kind derives from
# the corpus itself (e.g. the multiterm token-skip rules).
Synthesizer = Callable[[IndexSpec, list, list], list]

_SYNTHESIZERS: dict[str, Synthesizer] = {}


def register_rule_synthesizer(kind: str):
    """Register a corpus-driven rule synthesizer for an index kind."""

    def deco(fn: Synthesizer) -> Synthesizer:
        if kind in _SYNTHESIZERS:
            raise ValueError(f"synthesizer for kind {kind!r} already "
                             "registered")
        _SYNTHESIZERS[kind] = fn
        return fn

    return deco


def get_synthesizer(kind: str) -> Synthesizer | None:
    return _SYNTHESIZERS.get(kind)


def get_builder(kind: str) -> Builder:
    try:
        return _BUILDERS[kind]
    except KeyError:
        raise ValueError(
            f"unknown index kind {kind!r}; registered kinds: "
            f"{registered_kinds()}") from None


def registered_kinds() -> list[str]:
    return sorted(_BUILDERS)
