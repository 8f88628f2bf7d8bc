"""Compile the completion path for a TPU v5e that is described, not attached.

The TPU compiler is installed here, so each program is lowered and
compiled for one chip of a ``v5e:2x2`` topology, at the table widths of
the USPS deployment at its published 1,000,000 strings.  This catches
what interpret mode cannot: Mosaic's tiling rules, primitives it does not
lower, and programs that do not fit the chip's memory.  Nothing runs.

The topology is described inside a module fixture and nowhere else: the
TPU library may be loaded by one process at a time, so describing it at
import time would make parallel test workers collect different tests.

The jnp engine (what ``substrate="auto"`` resolves to) must compile.
Each Pallas kernel family that Mosaic still refuses is a strict xfail
that carries the compiler's message; the port that makes it compile has
to turn its xfail into a plain test.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import IndexSpec, build_index
from repro.core import engine as eng
from repro.core import make_rules
from repro.data.strings import make_usps
from repro.kernels import ops

N_SMALL = 2000          # strings actually built here (tables fit VMEM)
N_FULL = 1_000_000      # the USPS config's published size
# dictionary-sized tables: their leading dimension grows with the strings
DICT_FIELDS = (
    "depth", "max_score", "leaf_score", "leaf_sid", "syn_mask", "tout",
    "first_child", "edge_char", "edge_child", "s_first_child",
    "s_edge_char", "s_edge_child", "emit_ptr", "emit_node", "emit_score",
    "emit_is_leaf", "tele_plane", "link_ptr", "link_rule", "link_target",
    "topk_score", "topk_sid")
HBM_BYTES = 16 * 10**9  # one v5e chip
B, L, K, BLOCK = 64, 32, 10, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler to describe it with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache off meanwhile
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def usps():
    ds = make_usps(n=N_SMALL, seed=0)
    return ds, make_rules(ds.rules)


def _index(usps, **spec):
    ds, rules = usps
    return build_index(ds.strings, ds.scores, rules, IndexSpec(**spec))


@pytest.fixture(scope="module")
def et(usps):
    """The serving launcher's default spec (beam phase 2)."""
    return _index(usps, kind="et")


@pytest.fixture(scope="module")
def et_cached(usps):
    """The USPS config's cache depth (cached-merge phase 2)."""
    return _index(usps, kind="et", cache_k=16)


def _sds(x, sharding, scale: bool = False, name: str = ""):
    shape = tuple(x.shape)
    if scale and name in DICT_FIELDS and shape[0] > 1:
        shape = (shape[0] * (N_FULL // N_SMALL),) + shape[1:]
    return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)


def _trie(dev, sharding, full: bool = True):
    """The DeviceTrie as shapes on the chip, at USPS-1M size if ``full``."""
    return eng.DeviceTrie(**{
        f: None if getattr(dev, f) is None
        else _sds(getattr(dev, f), sharding, full, f)
        for f in eng.DeviceTrie._fields})


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(jitted, *args):
    compiled = jitted.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"
    return compiled.as_text()


# -- the jnp engine: what substrate="auto" serves with --------------------


@pytest.mark.parametrize("which", ["beam", "cached"])
def test_complete_batch_compiles(which, et, et_cached, one_chip):
    idx = et if which == "beam" else et_cached
    assert idx.substrate == eng.resolve_substrate("auto")
    run = idx._fn(B, L, K, idx.cfg)
    text = _compile(run.func, _trie(idx.device, one_chip),
                    _arg((B, L), jnp.int32, one_chip),
                    _arg((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" not in text


def test_session_advance_and_topk_compile(et, one_chip):
    init, advance, topk = et._session_fns(K)
    dev = _trie(et.device, one_chip)
    state = jax.tree.map(lambda s: _arg(s.shape, s.dtype, one_chip),
                         jax.eval_shape(init.func, dev))
    _compile(advance.func, dev, state, _arg((), jnp.int32, one_chip))
    _compile(topk.func, dev, state)


def test_slab_advance_and_topk_compile(et, one_chip):
    init, advance = et._slab_fns(BLOCK)
    dev = _trie(et.device, one_chip)
    slab = jax.tree.map(lambda s: _arg(s.shape, s.dtype, one_chip),
                        jax.eval_shape(init.func, dev))
    _compile(advance.func, dev, slab, _arg((BLOCK,), jnp.int32, one_chip),
             _arg((BLOCK,), jnp.bool_, one_chip))
    _compile(et._slab_topk_fn(BLOCK, K).func, dev, slab)


# -- the Pallas kernels, compiled (interpret=False) -----------------------


def test_topk_select_compiles(monkeypatch, one_chip):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    scores = _arg((B, 320), jnp.int32, one_chip)
    text = _compile(jax.jit(lambda s, p: ops.topk_select(s, p, K)),
                    scores, scores)
    assert "tpu_custom_call" in text


def _refused(message: str):
    return pytest.mark.xfail(strict=True, raises=NotImplementedError,
                             reason=f"Mosaic refuses it: {message}")


DYNAMIC_SLICE = ("Unimplemented primitive in Pallas TPU lowering for "
                 "KernelType.TC: dynamic_slice (per-lane jnp.take from a "
                 "table)")
SCATTER = ("Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: scatter (the frontier dedup's rank scatter; "
           "per-lane table gathers follow)")
GATHER_2D = "Only 2D gather is supported (per-lane jnp.take from a table)"


@pytest.mark.parametrize("kernel", [
    pytest.param("trie_walk-resident", marks=_refused(DYNAMIC_SLICE)),
    pytest.param("trie_walk-streamed", marks=_refused(DYNAMIC_SLICE)),
    pytest.param("locus_walk-resident", marks=_refused(SCATTER)),
    pytest.param("locus_walk-streamed", marks=_refused(SCATTER)),
    pytest.param("beam_topk-resident", marks=_refused(GATHER_2D)),
    pytest.param("beam_topk-streamed", marks=_refused(DYNAMIC_SLICE)),
    pytest.param("cached_topk_merge", marks=_refused(GATHER_2D)),
])
def test_kernel_compiles(kernel, usps, et, et_cached, monkeypatch,
                         one_chip):
    """Resident tiers at the small build's tables (they fit the VMEM
    budget); streamed tiers at USPS-1M tables in HBM."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    family, _, tier = kernel.partition("-")
    streamed = tier == "streamed"
    qs = _arg((B, L), jnp.int32, one_chip)
    qlens = _arg((B,), jnp.int32, one_chip)
    loci = _arg((B, et.cfg.frontier), jnp.int32, one_chip)
    if family == "trie_walk":
        plain = _index(usps, kind="plain")
        t = _trie(plain.device, one_chip, full=streamed)
        fn = jax.jit(lambda t, q, ql: ops.trie_walk(
            t.first_child, t.edge_char, t.edge_child, q, ql,
            streamed=streamed, walk_tile=plain.cfg.walk_tile))
        args = (t, qs, qlens)
    elif family == "locus_walk":
        fn = jax.jit(lambda t, q, ql: ops.locus_walk(
            t, et.cfg, q, ql, streamed=streamed))
        args = (_trie(et.device, one_chip, full=streamed), qs, qlens)
    elif family == "beam_topk":
        fn = jax.jit(lambda t, lo: ops.beam_topk(
            t, et.cfg, lo, K, streamed=streamed))
        args = (_trie(et.device, one_chip, full=streamed), loci)
    else:
        dev = et_cached.device
        fn = jax.jit(lambda lo, s, i: ops.cached_topk_merge(lo, s, i, K))
        args = (loci, _sds(dev.topk_score, one_chip),
                _sds(dev.topk_sid, one_chip))
    assert "tpu_custom_call" in _compile(fn, *args)
