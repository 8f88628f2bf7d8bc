"""Substrate-routed cross-shard top-k merge, single-process local mode.

Unlike :mod:`tests.test_distributed` (skip-gated on the modern shard_map
APIs), everything here runs on the container jax: the local path stacks
every shard's trie on one device and fuses the per-shard answers through
the same :func:`repro.core.distributed.merge_shard_topk` the mesh path
uses, so the sharded index stays fully exercised without a mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import IndexSpec, build_index
from repro.core import engine as eng
from repro.core import make_rules
from repro.core.distributed import ShardedCompletionIndex, merge_shard_topk
from repro.core.oracle import OracleIndex
from repro.data.strings import make_usps, make_workload


@pytest.fixture(scope="module")
def corpus():
    strings = [f"record {i:03d} entry" for i in range(64)] + [
        "andrew pavlo", "william smith"]
    scores = list(range(1, len(strings) + 1))
    rules = make_rules([("andy", "andrew"), ("bill", "william"),
                        ("rec", "record")])
    return strings, scores, rules


QUERIES = ["andy", "bill s", "rec 00", "record 01", "zzz", "entry", "r",
           "re", "", "record 063 entry x"]


# -- merge primitive ----------------------------------------------------------


@pytest.mark.parametrize("substrate", ["jnp", "pallas"])
def test_merge_shard_topk_matches_lax_oracle(substrate):
    """[S, B, k] per-shard answers fuse to the same global top-k as a
    plain lax.top_k over the concatenated candidates, payloads aligned."""
    rng = np.random.default_rng(0)
    S, B, k = 4, 6, 5
    scores = rng.integers(-1, 1000, (S, B, k)).astype(np.int32)
    # descending within each shard row, like real per-shard answers
    scores = -np.sort(-scores, axis=-1)
    gsids = rng.integers(0, 10_000, (S, B, k)).astype(np.int32)
    sub = eng.get_substrate(substrate)
    got_s, got_i = merge_shard_topk(jnp.asarray(scores), jnp.asarray(gsids),
                                    k, sub)
    flat = np.moveaxis(scores, 0, 1).reshape(B, S * k)
    flat_i = np.moveaxis(gsids, 0, 1).reshape(B, S * k)
    ref_s, ref_pos = jax.lax.top_k(jnp.asarray(flat), k)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ref_s))
    np.testing.assert_array_equal(
        np.asarray(got_i), np.take_along_axis(flat_i, np.asarray(ref_pos),
                                              axis=1))


# -- local mode vs oracle ------------------------------------------------------


@pytest.mark.parametrize("n_shards", [1, 3])
def test_local_sharded_matches_oracle(corpus, n_shards):
    strings, scores, rules = corpus
    oracle = OracleIndex(strings, scores, rules)
    idx = ShardedCompletionIndex(strings, scores, rules, n_shards=n_shards,
                                 kind="ht", alpha=0.5)
    assert idx.mesh is None
    got = idx.complete(QUERIES, k=5)
    for q, row in zip(QUERIES, got):
        assert [s for s, _ in row] == \
            [s for s, _ in oracle.complete(q, 5)], q


def test_local_sharded_matches_single_index(corpus):
    """Hash-sharding + merge must be invisible: identical answers (scores
    and strings) to one unsharded index over the same dictionary."""
    strings, scores, rules = corpus
    single = build_index(strings, scores, rules, IndexSpec(kind="et"))
    sharded = ShardedCompletionIndex(strings, scores, rules, n_shards=3,
                                     kind="et")
    assert sharded.complete(QUERIES, k=5) == single.complete(QUERIES, k=5)


def test_local_sharded_usps_workload():
    ds = make_usps(n=600, seed=1)
    rules = make_rules(ds.rules)
    single = build_index(ds.strings, ds.scores, rules, IndexSpec(kind="et"))
    sharded = ShardedCompletionIndex(ds.strings, ds.scores, rules,
                                     n_shards=4, kind="et")
    qs = make_workload(ds, 24, seed=7)
    assert sharded.complete(qs, k=10) == single.complete(qs, k=10)


def test_local_batch_bucketing_reuses_compiles(corpus):
    strings, scores, rules = corpus
    idx = ShardedCompletionIndex(strings, scores, rules, n_shards=2,
                                 kind="et")
    idx.complete(["an", "re", "w"], k=5)        # B=3 -> bucket 4
    misses0 = idx._compile_cache.misses
    idx.complete(["andy", "bill", "rec", "en"], k=5)   # B=4: same bucket
    assert idx._compile_cache.misses == misses0
    assert idx._compile_cache.hits >= 1


# -- construction / persistence ------------------------------------------------


def test_requires_mesh_or_n_shards(corpus):
    strings, scores, rules = corpus
    with pytest.raises(TypeError, match="mesh= .*or n_shards="):
        ShardedCompletionIndex(strings, scores, rules, kind="et")


def test_save_load_roundtrip_local(tmp_path, corpus):
    strings, scores, rules = corpus
    idx = ShardedCompletionIndex(strings, scores, rules, n_shards=3,
                                 kind="et", cache_k=4)
    path = str(tmp_path / "sharded")
    idx.save(path)
    loaded = ShardedCompletionIndex.load(path)
    assert loaded.mesh is None
    assert loaded.spec == idx.spec
    assert len(loaded.shards) == 3
    assert loaded.complete(QUERIES, k=5) == idx.complete(QUERIES, k=5)


# -- targeted serving errors ---------------------------------------------------


def test_session_raises_targeted_error(corpus):
    strings, scores, rules = corpus
    idx = ShardedCompletionIndex(strings, scores, rules, n_shards=2,
                                 kind="et")
    with pytest.raises(NotImplementedError, match="locus frontier"):
        idx.session(k=5)


def test_service_open_session_raises_targeted_error(corpus):
    """CompletionService.open_session on a sharded index must fail with
    the explanation (and point at complete()), not an AttributeError from
    deep inside the session plumbing — batch serving keeps working."""
    from repro.serving import CompletionService

    strings, scores, rules = corpus
    svc = CompletionService(ShardedCompletionIndex(
        strings, scores, rules, n_shards=2, kind="et"))
    with pytest.raises(NotImplementedError,
                       match="local CompletionIndex") as ei:
        svc.open_session(k=5)
    assert "complete()" in str(ei.value)
    out = svc.complete(["andy"], k=3)
    assert out[0][0][1] == "andrew pavlo"


def test_targeted_errors_are_the_dedicated_type(corpus):
    """The session-shaped entry points raise UnsupportedOnShardedIndex
    (a NotImplementedError subclass, so older match-based callers keep
    working) rather than a bare NotImplementedError."""
    from repro.core.distributed import UnsupportedOnShardedIndex

    assert issubclass(UnsupportedOnShardedIndex, NotImplementedError)
    strings, scores, rules = corpus
    idx = ShardedCompletionIndex(strings, scores, rules, n_shards=2,
                                 kind="et")
    with pytest.raises(UnsupportedOnShardedIndex):
        idx.session(k=5)
    with pytest.raises(UnsupportedOnShardedIndex):
        idx.open_session(k=5)


def test_service_compact_raises_targeted_error(corpus):
    """compact() is an overlay operation; on a sharded index the service
    points at the per-shard workaround instead of AttributeError-ing."""
    from repro.core.distributed import UnsupportedOnShardedIndex
    from repro.serving import CompletionService

    strings, scores, rules = corpus
    svc = CompletionService(ShardedCompletionIndex(
        strings, scores, rules, n_shards=2, kind="et"))
    with pytest.raises(UnsupportedOnShardedIndex, match="per-shard"):
        svc.compact()


# -- packed layout is rejected at spec validation, not deep in stacking --------


def test_packed_spec_rejected_at_construction(corpus):
    strings, scores, rules = corpus
    with pytest.raises(ValueError, match="unsupported on sharded"):
        ShardedCompletionIndex(strings, scores, rules, n_shards=2,
                               kind="et", compression="packed")


def test_packed_shards_rejected_by_from_shards(corpus):
    """Pre-built packed shards fail at wrap time with the workaround in
    the message (build with compression='none'), before any stacking."""
    strings, scores, rules = corpus
    spec = IndexSpec(kind="et", compression="packed")
    shards = [build_index(strings[i::2], scores[i::2], rules, spec)
              for i in range(2)]
    with pytest.raises(ValueError, match="compression='none'"):
        ShardedCompletionIndex.from_shards(shards)


def test_packed_spec_still_validates_unsharded():
    """The rejection is sharded-only: the same spec stays buildable as a
    local index (regression guard for the validate/validate_sharded
    split)."""
    spec = IndexSpec(kind="et", compression="packed")
    assert spec.validate() is spec
