"""Substrate parity: `complete` / `Session` results must be bit-identical
across the `jnp` reference and `pallas` (interpret mode on CPU) substrates
for every index kind, both phase-2 engines, and the exactness-retry path.

Parity here is the acceptance gate for the pluggable-substrate seam: any
kernel routed in by the pallas substrate (batched trie walk, topk_select,
cached locus gather+merge) must reproduce the reference engine exactly.
"""

import numpy as np
import pytest


from repro.api import IndexSpec, Session, build_index
from repro.core import engine as eng
from repro.core import make_rules
from repro.core.oracle import OracleIndex

KINDS = ["plain", "tt", "et", "ht"]

QUERIES = ["andy pa", "andrew pa", "bil", "bill of", "a", "w", "andrew",
           "andrew pavlo", "xyz", "", "andy pavloz"]


@pytest.fixture(scope="module")
def paper_data():
    strings = ["andrew pavlo", "andrew parker", "andrew packard",
               "william smith", "bill of rights"]
    scores = [50, 40, 30, 20, 10]
    rules = make_rules([("andy", "andrew"), ("bill", "william")])
    return strings, scores, rules


def _build(paper_data, kind, **kw):
    strings, scores, rules = paper_data
    return build_index(strings, scores, rules, IndexSpec(kind=kind, **kw))


# -- registry / resolution ----------------------------------------------------


def test_registry_has_both_substrates():
    assert {"jnp", "pallas"} <= set(eng.available_substrates())
    assert isinstance(eng.get_substrate("pallas"), eng.PallasSubstrate)
    with pytest.raises(ValueError, match="unknown substrate"):
        eng.get_substrate("cuda")


def test_auto_resolves_by_backend():
    # jnp on every backend: the pallas kernels do not compile for a TPU
    # yet (tests/test_tpu_compile.py), so auto never picks them
    assert eng.resolve_substrate("auto") == "jnp"
    assert eng.resolve_substrate("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown substrate"):
        eng.resolve_substrate("nope")


def test_spec_validates_substrate(paper_data):
    with pytest.raises(ValueError, match="unknown substrate"):
        IndexSpec(kind="et", substrate="cuda").validate()
    idx = _build(paper_data, "et", substrate="pallas")
    assert idx.substrate == "pallas"
    assert idx.cfg.substrate == "pallas"    # rides the jit key


def test_substrate_joins_compile_cache_key(paper_data):
    idx = _build(paper_data, "et")
    idx.set_substrate("jnp")
    idx.complete(["an"], k=3)
    misses0 = idx._compile_cache.misses
    idx.set_substrate("pallas")
    idx.complete(["an"], k=3)               # same shapes, new substrate
    assert idx._compile_cache.misses == misses0 + 1
    idx.set_substrate("jnp")
    idx.complete(["an"], k=3)               # old executable still cached
    assert idx._compile_cache.misses == misses0 + 1


# -- batch parity -------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("cache_k", [0, 8])
def test_complete_parity_all_kinds(paper_data, kind, cache_k):
    idx = _build(paper_data, kind, cache_k=cache_k)
    r_jnp = idx.set_substrate("jnp").complete(QUERIES, k=3)
    r_pal = idx.set_substrate("pallas").complete(QUERIES, k=3)
    assert r_jnp == r_pal
    # and both match the host-side oracle (plain kind ignores rules)
    strings, scores, rules = paper_data
    oracle = OracleIndex(strings, scores, rules if kind != "plain" else [])
    for q, row in zip(QUERIES, r_jnp):
        assert [s for s, _ in row] == [s for s, _ in oracle.complete(q, 3)], q


@pytest.mark.parametrize("cache_k", [0, 4])
def test_complete_parity_nonbucket_batches(paper_data, cache_k):
    """Batch sizes off the kernel block grid exercise the ops.py padding."""
    idx = _build(paper_data, "plain", cache_k=cache_k)
    for qs in (["andrew"], QUERIES[:5], QUERIES[:9], QUERIES * 3):
        assert idx.set_substrate("jnp").complete(qs, k=2) == \
            idx.set_substrate("pallas").complete(qs, k=2)


# -- session parity -----------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_session_parity_all_kinds(paper_data, kind):
    idx = _build(paper_data, kind, cache_k=8)
    typed = "andy pa"
    outs = {}
    for substrate in ("jnp", "pallas"):
        idx.set_substrate(substrate)
        sess = Session(idx, k=3)
        rows = [sess.type(ch) for ch in typed]
        rows.append(sess.backspace(2))
        rows.append(sess.type("v"))
        outs[substrate] = rows
    assert outs["jnp"] == outs["pallas"]
    # per-keystroke results equal the one-shot path (on the last substrate)
    assert outs["pallas"][-1] == idx.complete(["andy v"], k=3)[0]


# -- exactness-retry parity ---------------------------------------------------


@pytest.mark.parametrize("kind", ["tt", "ht"])
def test_retry_path_parity(paper_data, kind):
    """Starved widths force the inexact flag; the widened host-side retry
    must converge to identical results on both substrates."""
    tiny = _build(paper_data, kind, frontier=2, gens=2, expand=2,
                  max_steps=4)
    wide = _build(paper_data, kind)
    qs = ["an", "andy pa", "bill", "a"]
    expect = wide.complete(qs, k=3)
    for substrate in ("jnp", "pallas"):
        assert tiny.set_substrate(substrate).complete(qs, k=3) == expect
    # session fallback routes through the same retry machinery
    sess = Session(tiny.set_substrate("pallas"), k=3)
    assert sess.type("andy pa") == expect[1]


# -- engine-level entry points ------------------------------------------------


def test_complete_batch_matches_complete_one(paper_data):
    from repro.core.alphabet import pad_queries

    idx = _build(paper_data, "et", cache_k=4)
    qs, qlens = pad_queries(["andy", "bil", "zz", ""], 8)
    for substrate in ("jnp", "pallas"):
        sub = eng.get_substrate(substrate)
        bs, bi, be = eng.complete_batch(idx.device, idx.cfg, qs, qlens, 3,
                                        sub)
        for b in range(qs.shape[0]):
            s1, i1, e1 = eng.complete_one(idx.device, idx.cfg, qs[b],
                                          qlens[b], 3, sub)
            np.testing.assert_array_equal(np.asarray(bs[b]), np.asarray(s1))
            np.testing.assert_array_equal(np.asarray(bi[b]), np.asarray(i1))
            assert bool(be[b]) == bool(e1)


def test_pallas_rule_free_walk_matches_locus_dp(paper_data):
    """The pallas trie-walk fast path (plain kind) must land on the same
    loci as the reference frontier DP."""
    from repro.core.alphabet import pad_queries

    idx = _build(paper_data, "plain")
    t, cfg = idx.device, idx.cfg
    sub = eng.get_substrate("pallas")
    assert sub._rule_free(t, cfg)
    qs, qlens = pad_queries(["andrew", "andrew pa", "x", ""], 12)
    loci_p, ov_p = sub.walk_batch(t, cfg, qs, qlens)
    loci_j, ov_j = eng.get_substrate("jnp").walk_batch(t, cfg, qs, qlens)
    np.testing.assert_array_equal(np.asarray(loci_p), np.asarray(loci_j))
    np.testing.assert_array_equal(np.asarray(ov_p), np.asarray(ov_j))


# -- fused locus-DP kernel (rule-bearing walk) --------------------------------


def _walk_parity(idx, queries, max_len):
    """Assert pallas walk_batch == jnp walk_batch bit-for-bit; returns the
    (jnp) overflow vector for extra assertions."""
    from repro.core.alphabet import pad_queries

    t, cfg = idx.device, idx.cfg
    qs, qlens = pad_queries(queries, max_len)
    loci_p, ov_p = eng.get_substrate("pallas").walk_batch(t, cfg, qs, qlens)
    loci_j, ov_j = eng.get_substrate("jnp").walk_batch(t, cfg, qs, qlens)
    np.testing.assert_array_equal(np.asarray(loci_p), np.asarray(loci_j))
    np.testing.assert_array_equal(np.asarray(ov_p), np.asarray(ov_j))
    return np.asarray(ov_j)


@pytest.mark.parametrize("kind", ["tt", "et", "ht"])
def test_fused_walk_claims_rule_bearing_kinds(paper_data, kind):
    """tt/et/ht walks are no longer a jnp fallback: the pallas substrate
    probes capable and its fused kernel reproduces the reference DP."""
    idx = _build(paper_data, kind)
    t, cfg = idx.device, idx.cfg
    sub = eng.get_substrate("pallas")
    assert not sub._rule_free(t, cfg)
    assert sub.can_walk_batch(t, cfg, 16)
    assert sub.walk_variant(t, cfg, 16) == "resident"
    _walk_parity(idx, QUERIES, 16)


@pytest.mark.parametrize("kind", ["tt", "et", "ht"])
def test_fused_walk_overflow_frontier_parity(paper_data, kind):
    """A starved frontier forces dedup-compaction drops; the kernel's
    overflow accounting must match the reference exactly (it gates the
    exactness flag and thus the host-side retry)."""
    idx = _build(paper_data, kind, frontier=1)
    ov = _walk_parity(idx, QUERIES + ["andy", "bill", "bill of ri"], 16)
    assert (ov > 0).any()   # F=1 cannot hold literal node + rule target


def test_fused_walk_nonbucket_batches(paper_data):
    """Rule-bearing batches off the kernel block grid exercise the ops.py
    padding (padded rows walk to the root and slice off)."""
    idx = _build(paper_data, "ht")
    for qs in (["andy"], QUERIES[:3], QUERIES[:7], QUERIES * 2):
        _walk_parity(idx, qs, 12)


def test_fused_walk_probe_envelope_falls_back(paper_data):
    """Configs outside the kernel's static envelope are refused by the
    probe, and walk_batch still answers (via the inherited jnp DP) with
    identical results."""
    sub = eng.get_substrate("pallas")
    idx = _build(paper_data, "ht",
                 frontier=2 * sub._FUSE_MAX_FRONTIER)
    t, cfg = idx.device, idx.cfg
    assert not sub.can_walk_batch(t, cfg, 16)
    _walk_parity(idx, QUERIES[:4], 16)
    # the probe is about width/length, not kind: the same trie at default
    # widths is claimed
    assert sub.can_walk_batch(_build(paper_data, "ht").device,
                              _build(paper_data, "ht").cfg, 16)


@pytest.mark.parametrize("substrate,want", [
    ("jnp", ["beam=jnp", "walk=jnp"]),
    ("pallas", ["beam=pallas-resident", "walk=pallas-resident"]),
])
def test_traced_paths_record_the_dispatch(paper_data, substrate, want):
    """The dispatch record names the code of the programs traced inside
    it; a probe that declines on pallas shows up as the jnp code it fell
    back to."""
    from repro.core.engine.primitives import record_dispatch

    idx = _build(paper_data, "et", substrate=substrate)
    with record_dispatch() as paths:
        idx.complete(["andy pa"], k=3)
    assert sorted(paths) == want
    with record_dispatch() as paths:
        idx.complete(["andy pa"], k=3)   # compiled already: not traced
        Session(idx, k=3).type("a")
    assert sorted(paths) == ["advance=jnp"] + want[:1]
    if substrate == "pallas":
        sub = eng.get_substrate("pallas")
        wide = _build(paper_data, "et", substrate="pallas",
                      gens=2 * sub._BEAM_MAX_GENS)
        with record_dispatch() as paths:
            wide.complete(["andy pa"], k=3)
        assert sorted(paths) == ["beam=jnp", "walk=pallas-resident"]


def test_fused_walk_session_and_batch_agree(paper_data):
    """End-to-end: per-keystroke sessions (which reuse the packed rule
    planes incrementally) and the fused batch walk give the same answers
    on the pallas substrate."""
    idx = _build(paper_data, "ht", cache_k=8).set_substrate("pallas")
    sess = Session(idx, k=3)
    rows = [sess.type(ch) for ch in "andy pa"]
    assert rows[-1] == idx.complete(["andy pa"], k=3)[0]


# -- fused beam phase-2 kernel ------------------------------------------------


def _beam_parity(idx, queries, k, max_len=16):
    """Assert pallas beam_topk_batch == jnp beam_topk_batch bit-for-bit
    (scores, sids, exact); returns the (jnp) exact vector."""
    from repro.core.alphabet import pad_queries

    t, cfg = idx.device, idx.cfg
    qs, qlens = pad_queries(queries, max_len)
    loci, _ = eng.get_substrate("jnp").walk_batch(t, cfg, qs, qlens)
    a = eng.get_substrate("pallas").beam_topk_batch(t, cfg, loci, k)
    b = eng.get_substrate("jnp").beam_topk_batch(t, cfg, loci, k)
    for x, y, nm in zip(a, b, ("scores", "sids", "exact")):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=nm)
    return np.asarray(b[2])


@pytest.mark.parametrize("kind", KINDS)
def test_fused_beam_claims_all_kinds(paper_data, kind):
    """Beam phase 2 is no longer a jnp-everywhere phase: the pallas
    substrate probes capable at the default widths and its fused kernel
    reproduces the reference priority search on every index kind."""
    idx = _build(paper_data, kind)
    sub = eng.get_substrate("pallas")
    assert sub.can_beam_batch(idx.device, idx.cfg, 3)
    _beam_parity(idx, QUERIES, 3)


def test_fused_beam_probe_envelope_falls_back(paper_data):
    """Configs outside the kernel's static envelope are refused by the
    probe, and beam_topk_batch still answers (via the inherited vmapped
    reference) with identical results."""
    sub = eng.get_substrate("pallas")
    idx = _build(paper_data, "et", gens=2 * sub._BEAM_MAX_GENS)
    assert not sub.can_beam_batch(idx.device, idx.cfg, 3)
    _beam_parity(idx, QUERIES[:4], 3)
    # k is part of the probe too
    small = _build(paper_data, "et")
    assert sub.can_beam_batch(small.device, small.cfg, 3)
    assert not sub.can_beam_batch(small.device, small.cfg,
                                  sub._BEAM_MAX_K + 1)


def test_fused_beam_retry_rounds_reprobe(paper_data):
    """The host-side exactness retry widens the config 4x per round and
    re-dispatches through the substrate: round 1 stays inside the kernel
    envelope at default widths, later rounds fall back to jnp."""
    from dataclasses import replace

    idx = _build(paper_data, "tt")
    sub = eng.get_substrate("pallas")
    cfg1 = replace(idx.cfg, frontier=idx.cfg.frontier * 2,
                   gens=idx.cfg.gens * 4, max_steps=idx.cfg.max_steps * 4,
                   use_cache=False)
    assert sub.can_beam_batch(idx.device, cfg1, 3)
    cfg2 = replace(cfg1, frontier=cfg1.frontier * 2, gens=cfg1.gens * 4,
                   max_steps=cfg1.max_steps * 4)
    assert not sub.can_beam_batch(idx.device, cfg2, 3)


# -- exactness: strict admissible bound on score ties -------------------------


def test_beam_tie_drop_stays_exact():
    """Regression (strict dropped_max bound): a pool drop whose bound
    EQUALS the final k-th score ties at best — it must stay exact on both
    substrates instead of triggering a spurious doubled-width retry."""
    from repro.core.alphabet import pad_queries

    strings = [f"a{chr(98 + i)}x" for i in range(10)]
    idx = build_index(strings, [5] * 10, make_rules([]),
                      IndexSpec(kind="plain", gens=2, expand=1, frontier=2,
                                max_steps=64))
    qs, qlens = pad_queries(["a"], 4)
    for substrate in ("jnp", "pallas"):
        sub = eng.get_substrate(substrate)
        s, i, e = eng.complete_batch(idx.device, idx.cfg, qs, qlens, 2, sub)
        assert np.asarray(s)[0].tolist() == [5, 5], substrate
        # the starved (W=2, P=1) pool provably drops bound-5 candidates
        # here; with a non-strict bound this flag flips to False
        assert bool(np.asarray(e)[0]), substrate
    # end-to-end: exactly one compiled executable — no widened retry
    idx.set_substrate("pallas")
    assert [s for s, _ in idx.complete(["a"], k=2)[0]] == [5, 5]
    assert idx._compile_cache.misses == 1


# -- persistence: rule-plane container migration ------------------------------


def _rewrite_as_v1(path):
    """Strip the packed rule plane from a saved container and stamp it as
    format_version 1 — byte-level shape of a pre-relayout index."""
    import json

    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    for k in ("trie__tele_plane", "trie__link_ptr", "rule_trie__term_plane"):
        assert k in arrays, f"v2 container should carry {k}"
        del arrays[k]
    meta = json.loads(arrays["__meta__"].tobytes().decode())
    meta["format_version"] = 1
    for key in ("tele_width", "term_width"):
        meta["cfg"].pop(key, None)
    arrays["__meta__"] = np.frombuffer(json.dumps(meta).encode(),
                                       dtype=np.uint8)
    np.savez_compressed(path, **arrays)


@pytest.mark.parametrize("kind", ["tt", "ht"])
def test_load_v1_container_rebuilds_rule_planes(paper_data, kind, tmp_path):
    from repro.api import CompletionIndex

    idx = _build(paper_data, kind, cache_k=4)
    expect = idx.complete(QUERIES, k=3)
    path = str(tmp_path / "idx.npz")
    idx.save(path)
    _rewrite_as_v1(path)
    loaded = CompletionIndex.load(path)
    assert loaded.trie.tele_plane is not None
    assert loaded.trie.link_ptr is not None
    assert loaded.rule_trie.term_plane is not None
    assert loaded.cfg.tele_width == idx.cfg.tele_width
    assert loaded.cfg.term_width == idx.cfg.term_width
    for substrate in ("jnp", "pallas"):
        assert loaded.set_substrate(substrate).complete(QUERIES, k=3) \
            == expect


def test_load_rejects_mismatched_rule_plane(paper_data, tmp_path):
    """A container whose planes disagree with the recorded static widths
    must fail loudly at load, not mis-gather on device."""
    import json

    idx = _build(paper_data, "ht")
    path = str(tmp_path / "idx.npz")
    idx.save(path)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    plane = arrays["trie__tele_plane"]
    arrays["trie__tele_plane"] = np.concatenate(
        [plane, np.full_like(plane[:, :1], -1)], axis=1)
    meta = json.loads(arrays["__meta__"].tobytes().decode())
    np.savez_compressed(path, **arrays)
    from repro.api import CompletionIndex
    with pytest.raises(ValueError, match="rule plane"):
        CompletionIndex.load(path)


def test_persist_reresolves_substrate(paper_data, tmp_path):
    idx = _build(paper_data, "ht", cache_k=4)    # spec.substrate == "auto"
    path = str(tmp_path / "idx.npz")
    idx.save(path)
    from repro.api import CompletionIndex

    loaded = CompletionIndex.load(path)
    assert loaded.spec.substrate == "auto"
    assert loaded.substrate == eng.resolve_substrate("auto")
    assert loaded.complete(["andy pa"], k=3) == idx.complete(["andy pa"], k=3)
    # an explicitly pinned substrate survives the round-trip
    idx.set_substrate("pallas").save(path)
    assert CompletionIndex.load(path).substrate == "pallas"
