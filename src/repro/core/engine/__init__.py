"""Device-side top-k completion engine (array tries, substrate-dispatched).

The paper's best-first heap search (Alg. 2 / Alg. 4) is re-cast for TPU as:

  phase 1 — *locus DP* (:mod:`.locus`, :mod:`.incremental`): a fixed-width
      frontier sweep over query positions; the incremental variant carries
      the frontier across keystrokes.

  phase 2 — *top-k*: either the paper's priority search vectorized
      P-at-a-time (:mod:`.beam`) with an admissible-bound exactness flag,
      or the beyond-paper cached per-node top-K gather+merge
      (:mod:`.cached`), exact for k <= K.

Execution routes through a pluggable *substrate* (:mod:`.substrate`):
``"jnp"`` is the pure-jnp reference, ``"pallas"`` dispatches the batched
hot primitives (locus walk — rule-free and fused rule-bearing —, beam
priority search, cached gather+merge, top-k with payload) to the tuned
kernels in :mod:`repro.kernels`; every hot phase is substrate-pluggable.
The substrate name lives on :class:`EngineConfig` and therefore joins
every jit/compile-cache key.

Each phase's entry point runs under a ``jax.named_scope`` named after
its dispatch record (``record_dispatch``): ``engine.walk`` (the one-shot
locus DP), ``engine.advance`` (one keystroke of a session or slab) and
``engine.topk`` (phase 2, with the finalize/decode of carried loci), so
a device trace names the phase of every operation.

Everything here lowers under jit/vmap/shard_map with ShapeDtypeStruct
inputs, which is what the multi-pod dry-run exercises.
"""

from repro.core.engine.structs import (DeviceTrie, EngineConfig, INT_MAX,
                                       NEG_ONE)
from repro.core.engine.primitives import (csr_child_lookup, dedup_pad,
                                          iters_for, lower_bound)
from repro.core.engine.locus import (finalize_loci, link_lookup, locus_dp,
                                     match_table, teleport_expand)
from repro.core.engine.beam import beam_topk
from repro.core.engine.cached import cached_topk, gather_cached
from repro.core.engine.incremental import (LocusState, advance_loci,
                                           advance_loci_batch,
                                           advance_locus_state,
                                           init_locus_batch,
                                           init_locus_state, topk_from_loci,
                                           topk_from_loci_batch)
from repro.core.engine.overlay import DeltaOverlay, merge_overlay_topk
# substrate last: it pulls the sibling modules above off the (partially
# initialized) package, so they must already be bound
from repro.core.engine.substrate import (PallasSubstrate, Substrate,
                                         available_substrates,
                                         complete_batch, complete_one,
                                         get_substrate, register_substrate,
                                         resolve_substrate, topk_phase2,
                                         topk_phase2_batch)

__all__ = [
    "DeviceTrie", "EngineConfig", "INT_MAX", "NEG_ONE",
    "csr_child_lookup", "dedup_pad", "iters_for", "lower_bound",
    "match_table", "teleport_expand", "link_lookup", "finalize_loci",
    "locus_dp",
    "beam_topk", "cached_topk", "gather_cached",
    "LocusState", "init_locus_state", "advance_locus_state", "advance_loci",
    "topk_from_loci", "init_locus_batch", "advance_loci_batch",
    "topk_from_loci_batch",
    "DeltaOverlay", "merge_overlay_topk",
    "Substrate", "PallasSubstrate", "register_substrate", "get_substrate",
    "available_substrates", "resolve_substrate",
    "topk_phase2", "topk_phase2_batch", "complete_one", "complete_batch",
]
