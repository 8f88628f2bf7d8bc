"""repro.api v2 behaviour: IndexSpec registry dispatch, npz persistence
round-trips, incremental Session-vs-batch equivalence, and the bounded
compile cache."""

import dataclasses
import os
import re

import jax
import numpy as np
import pytest

from repro.api import (CompileCache, CompletionIndex, IndexSpec, Session,
                       bucket_size, build_index, register_builder,
                       registered_kinds)
from repro.core import make_rules
from repro.data.strings import make_usps, make_workload

KINDS = ["tt", "et", "ht", "plain"]


@pytest.fixture(scope="module")
def paper_example():
    strings = ["andrew pavlo", "andrew parker", "andrew packard",
               "william smith", "bill of rights"]
    scores = [50, 40, 30, 20, 10]
    rules = make_rules([("andy", "andrew"), ("bill", "william")])
    return strings, scores, rules


@pytest.fixture(scope="module")
def usps():
    ds = make_usps(n=1200, seed=0)
    return ds, make_rules(ds.rules)


# -- IndexSpec + registry -----------------------------------------------------


def test_spec_registry_dispatch_all_kinds(paper_example):
    """Every registered kind builds through the registry and the spec is
    recorded on the result."""
    strings, scores, rules = paper_example
    assert set(KINDS) <= set(registered_kinds())
    for kind in KINDS:
        spec = IndexSpec(kind=kind, alpha=0.4, cache_k=4)
        idx = build_index(strings, scores, rules, spec)
        assert idx.spec == spec
        assert idx.kind == kind
        assert idx.stats.kind == kind
    # kind-specific structure invariants prove per-kind builders really ran
    tt = build_index(strings, scores, rules, IndexSpec(kind="tt"))
    et = build_index(strings, scores, rules, IndexSpec(kind="et"))
    plain = build_index(strings, scores, rules, IndexSpec(kind="plain"))
    assert tt.stats.n_syn_nodes == 0 and tt.stats.n_links > 0
    assert et.stats.n_links == 0 and et.stats.n_syn_nodes > 0
    assert plain.stats.n_links == 0 and plain.stats.n_syn_nodes == 0


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="unknown index kind"):
        IndexSpec(kind="bogus").validate()
    with pytest.raises(ValueError, match="alpha"):
        IndexSpec(kind="ht", alpha=1.5).validate()
    with pytest.raises(ValueError, match="frontier"):
        IndexSpec(frontier=0).validate()
    with pytest.raises(TypeError):
        build_index(["a"], [1], [], spec=IndexSpec(), kind="et")


def test_register_builder_additive(paper_example):
    """A new kind is an additive registration, no core edits."""
    strings, scores, rules = paper_example
    name = "test-links-only"
    if name not in registered_kinds():
        @register_builder(name)
        def _links_only(ctx):
            n = len(ctx.rules)
            return np.zeros(n, bool), np.ones(n, bool)

    idx = build_index(strings, scores, rules, IndexSpec(kind=name))
    tt = build_index(strings, scores, rules, IndexSpec(kind="tt"))
    assert idx.complete(["andy pa"], k=3) == tt.complete(["andy pa"], k=3)


def test_build_backcompat_kwargs(paper_example):
    """Old keyword surface still works and matches the spec path."""
    strings, scores, rules = paper_example
    old = CompletionIndex.build(strings, scores, rules, kind="ht", alpha=0.3,
                                cache_k=8)
    new = build_index(strings, scores, rules,
                      IndexSpec(kind="ht", alpha=0.3, cache_k=8))
    assert old.spec == new.spec
    qs = ["andy", "bill", "a", "w"]
    assert old.complete(qs, 5) == new.complete(qs, 5)


# -- persistence --------------------------------------------------------------


def test_save_load_roundtrip(tmp_path, usps):
    """A loaded index answers identically to the freshly built one, with
    byte-identical BuildStats, and without re-running construction."""
    ds, rules = usps
    idx = build_index(ds.strings, ds.scores, rules,
                      IndexSpec(kind="ht", alpha=0.5, cache_k=8))
    path = str(tmp_path / "usps.npz")
    idx.save(path)
    loaded = CompletionIndex.load(path)
    assert dataclasses.asdict(loaded.stats) == dataclasses.asdict(idx.stats)
    assert loaded.spec == idx.spec
    assert loaded.cfg == idx.cfg
    assert loaded.strings == idx.strings
    for f in dataclasses.fields(idx.trie):
        a, b = getattr(idx.trie, f.name), getattr(loaded.trie, f.name)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f.name)
    qs = make_workload(ds, 48, seed=2)
    assert loaded.complete(qs, k=10) == idx.complete(qs, k=10)


def test_save_load_roundtrip_no_cache_no_rules(tmp_path):
    idx = build_index(["alpha", "beta", "betamax"], [3, 2, 1], [],
                      IndexSpec(kind="plain"))
    path = str(tmp_path / "plain.npz")
    idx.save(path)
    loaded = CompletionIndex.load(path)
    assert loaded.trie.topk_score is None
    assert loaded.complete(["b"], k=5) == idx.complete(["b"], k=5)


def test_load_rejects_bad_container(tmp_path):
    bad = str(tmp_path / "bad.npz")
    np.savez(bad, x=np.zeros(3))
    with pytest.raises(ValueError, match="not a repro completion-index"):
        CompletionIndex.load(bad)


# -- sessions -----------------------------------------------------------------


@pytest.mark.parametrize("kind", ["tt", "et", "ht"])
def test_session_matches_oneshot_per_keystroke(kind, usps):
    """Typing char-by-char through a Session yields exactly the one-shot
    ``complete`` answer at every prefix."""
    ds, rules = usps
    idx = build_index(ds.strings, ds.scores, rules,
                      IndexSpec(kind=kind, alpha=0.5))
    sess = idx.session(k=5)
    for q in make_workload(ds, 8, seed=3, max_len=10):
        sess.reset()
        for i, ch in enumerate(q):
            got = sess.type(ch)
            want = idx.complete([q[:i + 1]], k=5)[0]
            assert got == want, (q, q[:i + 1], kind)


def test_session_multichar_rules_and_backspace(paper_example):
    strings, scores, rules = paper_example
    idx = build_index(strings, scores, rules, IndexSpec(kind="tt"))
    sess = idx.session(k=3)
    assert sess.type("andy pa") == idx.complete(["andy pa"], k=3)[0]
    assert sess.prefix == "andy pa"
    assert sess.backspace(3) == idx.complete(["andy"], k=3)[0]
    assert sess.prefix == "andy"
    # keep typing after backspace
    assert sess.type(" pav") == idx.complete(["andy pav"], k=3)[0]
    sess.reset()
    assert sess.prefix == ""
    assert sess.type("bill") == idx.complete(["bill"], k=3)[0]


def test_session_cached_topk_path(paper_example):
    strings, scores, rules = paper_example
    idx = build_index(strings, scores, rules, IndexSpec(kind="et", cache_k=8))
    sess = Session(idx, k=3)
    for prefix in ("a", "an", "andy", "andy p"):
        sess.reset()
        assert sess.type(prefix) == idx.complete([prefix], k=3)[0], prefix


def test_advance_loci_scan_matches_locus_dp(paper_example):
    """The batched engine entry point (scan over a padded char vector) must
    land on the same loci/top-k as the per-char step and the one-shot DP."""
    import jax.numpy as jnp

    from repro.core import engine as eng
    from repro.core.alphabet import pad_queries

    strings, scores, rules = paper_example
    idx = build_index(strings, scores, rules, IndexSpec(kind="tt"))
    t, cfg = idx.device, idx.cfg
    for q in ("andy pa", "bill", "a", "xyz"):
        qs, qlens = pad_queries([q], 8)          # -1 padded beyond len(q)
        state = eng.advance_loci(t, cfg, eng.init_locus_state(t, cfg),
                                 jnp.asarray(qs[0]))
        assert int(state.length) == len(q)       # pads were no-ops
        s_inc, i_inc, e_inc = eng.topk_from_loci(t, cfg, state, 3)
        s_one, i_one, e_one = eng.complete_one(
            t, cfg, jnp.asarray(qs[0]), jnp.asarray(qlens[0]), 3)
        np.testing.assert_array_equal(np.asarray(s_inc), np.asarray(s_one), q)
        np.testing.assert_array_equal(np.asarray(i_inc), np.asarray(i_one), q)
        assert bool(e_inc) == bool(e_one), q


def test_service_session_stats(paper_example):
    from repro.serving import CompletionService

    strings, scores, rules = paper_example
    idx = build_index(strings, scores, rules, IndexSpec(kind="et"))
    svc = CompletionService(idx)
    sess = svc.open_session(k=3)
    out = sess.type("andy")
    assert [s for s, _ in out] == [50, 40, 30]
    assert svc.stats.n_keystrokes == 4
    assert len(svc.stats.keystroke_latencies_ms) == 4
    assert svc.stats.mean_keystroke_ms > 0
    assert svc.stats.p99_keystroke_ms() > 0
    svc.stats.reset_keystrokes()
    assert svc.stats.n_keystrokes == 0
    assert svc.stats.keystroke_latencies_ms == []


def test_inexact_retry_path_recovers(paper_example):
    """Deliberately starved widths force the exactness retry (regression:
    the widened pass used to crash writing into read-only jit output)."""
    strings, scores, rules = paper_example
    tiny = build_index(strings, scores, rules,
                       IndexSpec(kind="tt", frontier=2, gens=2, expand=2,
                                 max_steps=4))
    wide = build_index(strings, scores, rules, IndexSpec(kind="tt"))
    qs = ["an", "andy pa", "bill", "a"]
    assert tiny.complete(qs, k=3) == wide.complete(qs, k=3)
    # session fallback routes through the same retry machinery
    sess = tiny.session(k=3)
    assert sess.type("andy pa") == wide.complete(["andy pa"], k=3)[0]


def test_service_latency_window_bounded(paper_example):
    from repro.serving import completion_service as cs

    strings, scores, rules = paper_example
    idx = build_index(strings, scores, rules, IndexSpec(kind="et"))
    svc = cs.CompletionService(idx)
    stats = svc.stats
    stats.latencies_ms.extend([0.1] * cs.LATENCY_WINDOW)
    stats.keystroke_latencies_ms.extend([0.1] * cs.LATENCY_WINDOW)
    svc.complete(["a"], k=3)
    svc.open_session(k=3).type("an")
    assert len(stats.latencies_ms) == cs.LATENCY_WINDOW
    assert len(stats.keystroke_latencies_ms) == cs.LATENCY_WINDOW
    assert stats.n_keystrokes == 2          # counters unaffected by the cap


# -- compile cache ------------------------------------------------------------


def test_bucket_size():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(100) == 128
    assert bucket_size(3, minimum=1) == 4


def test_compile_cache_lru_bounded():
    cache = CompileCache(maxsize=2)
    a = cache.get("a", lambda: "va")
    assert cache.get("a", lambda: "XX") == "va"          # hit
    cache.get("b", lambda: "vb")
    cache.get("a", lambda: "XX")                          # refresh a
    cache.get("c", lambda: "vc")                          # evicts b (LRU)
    assert "b" not in cache and "a" in cache and "c" in cache
    assert len(cache) == 2
    assert cache.stats()["evictions"] == 1
    assert a == "va"


def test_index_compile_cache_buckets_batches(paper_example):
    """Nearby batch sizes share one compiled executable."""
    strings, scores, rules = paper_example
    idx = build_index(strings, scores, rules, IndexSpec(kind="et"))
    idx.complete(["a"], k=3)                            # batch bucket 1
    misses0 = idx._compile_cache.misses
    idx.complete(["a", "an", "and"], k=3)               # B=3 -> bucket 4
    idx.complete(["a", "an", "and", "andy"], k=3)       # B=4 -> bucket 4: hit
    assert idx._compile_cache.misses == misses0 + 1
    assert idx._compile_cache.hits >= 1


def test_batch_program_does_not_embed_the_index():
    """The jitted batch entry point takes the tables as arguments: two
    indexes of one config but different sizes lower to the same program
    text up to the shape numbers.  A closure over the tables would embed
    them as constants, growing the program with the dictionary.  (The
    binary searches unroll log2(table size) rounds, so both sizes keep
    every table within one power of two.)"""
    idxs = []
    for n in (500, 600):
        ds = make_usps(n=n, seed=0)
        idxs.append(build_index(ds.strings, ds.scores, make_rules(ds.rules),
                                IndexSpec(kind="et")))
    assert idxs[0].cfg == idxs[1].cfg
    assert idxs[0].device.first_child.shape != idxs[1].device.first_child.shape
    qs, qlens = np.full((8, 16), -1, np.int32), np.zeros((8,), np.int32)
    texts = []
    for idx in idxs:
        run = idx._fn(8, 16, 10, idx.cfg)
        texts.append(run.func.lower(*run.args, qs, qlens).as_text())
    shapeless = [re.sub(r"\d+", "N", t) for t in texts]
    assert shapeless[0] == shapeless[1]
    assert len(texts[0]) < 200_000


def test_persistent_cache_dir_honours_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to the one fixed directory inside the checkout."""
    from repro.api import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert cc.enable_persistent_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cc.enable_persistent_cache()
        assert path == str(cc.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
