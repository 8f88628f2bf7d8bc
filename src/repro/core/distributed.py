"""Distributed completion index: dictionary sharded across the `model` axis.

Strings are hash-partitioned into shards; each shard is an independent
TT/ET/HT over its subset with the (small) rule set replicated.  A query
batch is sharded over the data axes and replicated over `model`; every
device answers from its local sub-trie and a single all_gather + fused
top-k merge produces the global answer.  This is how the paper's
1M-string dictionaries scale to billions of strings across pods.

The cross-shard merge routes through ``Substrate.topk_with_payload``
(:func:`merge_shard_topk`) — the same seam the per-shard phase 2 uses —
so on the pallas substrate the [S*k]-candidate reduction runs the fused
top-k selection kernel instead of a host-side concat-and-sort.  Two
execution paths share that merge:

- :func:`sharded_complete`: ``jax.shard_map`` over a device mesh — every
  index constructed with a ``mesh`` answers here;
- :meth:`ShardedCompletionIndex._complete_local`: a single-process path
  that answers every shard from the stacked trie and fuses the merge in
  one jitted dispatch — the serving shape for one device carrying many
  shards (construct with ``mesh=None``).
"""

from __future__ import annotations

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.api import CompletionIndex, IndexSpec, build_index
from repro.api.compile_cache import CompileCache, bucket_size
from repro.core import engine as eng


class UnsupportedOnShardedIndex(NotImplementedError):
    """An operation that needs a local :class:`CompletionIndex` was called
    on a :class:`ShardedCompletionIndex` (or a service wrapping one).

    Raised instead of a bare ``NotImplementedError`` so callers can catch
    the *category* — per-keystroke sessions, mutation/compaction — and
    the message always names the local-mode alternative."""


def shard_strings(strings, scores, n_shards: int):
    """Hash-partition (deterministic, seed-free) strings into shards."""
    import zlib

    buckets = [([], []) for _ in range(n_shards)]
    for s, r in zip(strings, scores):
        b = s.encode() if isinstance(s, str) else bytes(s)
        h = zlib.crc32(b) % n_shards
        buckets[h][0].append(s)
        buckets[h][1].append(r)
    return buckets


def _pad_to(a: np.ndarray, shape) -> np.ndarray:
    pad = [(0, t - s) for s, t in zip(a.shape, shape)]
    if a.dtype == bool:
        return np.pad(a, pad, constant_values=False)
    if a.ndim == 1 and a.shape[0] > 0:
        return np.pad(a, pad, mode="edge")
    # 2-D planes (tele_plane, r_term_plane, topk_*) use -1 = empty, so
    # width padding across shards must stay inert, not point at node 0
    return np.pad(a, pad, constant_values=-1)


def stack_shards(indexes: list[CompletionIndex]):
    """Stack per-shard DeviceTries into one pytree with a leading shard dim.

    CSR pointer arrays are padded by repeating the last pointer (empty rows),
    data arrays by edge padding (never addressed past the real pointers).
    Returns (stacked DeviceTrie of numpy arrays, merged EngineConfig, stride).
    """
    devs = [ix.device for ix in indexes]
    fields = eng.DeviceTrie._fields
    cfgs = [ix.cfg for ix in indexes]
    if any(getattr(c, "compression", "none") != "none" for c in cfgs):
        raise NotImplementedError(
            "stack_shards does not support the compressed (packed) layout: "
            "padding would break the sorted side-table rank invariants — "
            "build shards with compression='none'")
    # the merged stream-tile widths are maxima over the shards, so every
    # streamable flat table keeps one merged tile of tail slack past the
    # longest shard — a streamed-tier window anchored at any real row
    # start stays in bounds on the stacked layout too (the same maxima
    # become the merged EngineConfig widths below, so the two stay
    # consistent by construction)
    walk_tile = max(c.walk_tile for c in cfgs)
    emit_tile = max(c.emit_tile for c in cfgs)
    link_tile = max(c.link_tile for c in cfgs)
    tile_slack = {
        "edge_char": walk_tile, "edge_child": walk_tile,
        "s_edge_char": walk_tile, "s_edge_child": walk_tile,
        "emit_node": emit_tile, "emit_score": emit_tile,
        "emit_is_leaf": emit_tile,
        "link_rule": link_tile, "link_target": link_tile,
    }
    stacked = {}
    for f in fields:
        vals = [getattr(d, f) for d in devs]
        if any(v is None for v in vals):
            # elided packed-only planes (always None once compression is
            # rejected above) — keep them None in the stacked trie too
            stacked[f] = None
            continue
        arrs = [np.asarray(v) for v in vals]
        tgt = tuple(max(a.shape[i] for a in arrs) for i in range(arrs[0].ndim))
        tgt = tuple(max(t, 1) for t in tgt)
        if f in tile_slack and tgt[0] > 1:
            tgt = (tgt[0] + tile_slack[f],) + tgt[1:]
        arrs = [_pad_to(a if a.size else np.zeros(tuple(1 for _ in tgt), a.dtype), tgt)
                for a in arrs]
        stacked[f] = np.stack(arrs)
    merged = eng.EngineConfig(
        frontier=max(c.frontier for c in cfgs),
        gens=max(c.gens for c in cfgs),
        expand=max(c.expand for c in cfgs),
        max_steps=max(c.max_steps for c in cfgs),
        rule_matches=max(c.rule_matches for c in cfgs),
        max_lhs_len=max(c.max_lhs_len for c in cfgs),
        max_terms_per_node=max(c.max_terms_per_node for c in cfgs),
        teleports=max(c.teleports for c in cfgs),
        tele_width=max(c.tele_width for c in cfgs),
        term_width=max(c.term_width for c in cfgs),
        walk_tile=walk_tile, emit_tile=emit_tile, link_tile=link_tile,
        memory_budget=max(c.memory_budget for c in cfgs),
        use_cache=all(c.use_cache for c in cfgs),
        cache_k=min(c.cache_k for c in cfgs),
        substrate=cfgs[0].substrate,   # shards share one IndexSpec
    )
    stride = max(len(ix.strings) for ix in indexes)
    return eng.DeviceTrie(**stacked), merged, stride


def merge_shard_topk(all_scores: jax.Array, all_gsids: jax.Array, k: int,
                     sub: eng.Substrate):
    """Fuse per-shard answers [S, B, k] into the global (scores[B, k],
    gsids[B, k]) with one substrate-routed top-k-with-payload.

    The candidate relayout is a device-side transpose+reshape feeding the
    substrate's selection (the fused ``topk_select`` kernel on pallas);
    score ties resolve toward the lower shard index then the lower
    per-shard rank — the same deterministic order on every substrate, so
    the shard_map and single-process paths agree bitwise."""
    S, B = all_scores.shape[0], all_scores.shape[1]
    flat_s = jnp.moveaxis(all_scores, 0, 1).reshape(B, S * k)
    flat_i = jnp.moveaxis(all_gsids, 0, 1).reshape(B, S * k)
    return sub.topk_with_payload(flat_s, flat_i, k)


def sharded_complete(stacked: eng.DeviceTrie, cfg: eng.EngineConfig,
                     qs: jax.Array, qlens: jax.Array, k: int, *,
                     mesh: jax.sharding.Mesh, sid_stride: int,
                     data_axes=("data",), model_axis: str = "model"):
    """Global top-k under shard_map: local per-shard top-k, then one
    all_gather over the model axis and the fused substrate merge.

    stacked: DeviceTrie with leading shard dim == mesh size along model axis.
    qs: int32[B, L] global batch; qlens int32[B].
    Returns (scores[B, k], global_sids[B, k]).
    """
    sub = eng.get_substrate(cfg.substrate)
    trie_spec = jax.tree.map(lambda _: P(model_axis), stacked,
                             is_leaf=lambda x: not isinstance(x, tuple))
    q_spec = P(data_axes)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(trie_spec, q_spec, q_spec),
             out_specs=(P(data_axes), P(data_axes)),
             check_vma=False)
    def run(trie, qs_l, qlens_l):
        local = jax.tree.map(lambda x: x[0], trie)  # drop unit shard dim
        scores, sids, _ = eng.complete_batch(local, cfg, qs_l, qlens_l, k,
                                             sub)
        shard = jax.lax.axis_index(model_axis)
        gsids = jnp.where(sids >= 0, sids + shard * sid_stride, -1)
        # merge across shards: all_gather to [S, b, k], then the fused
        # substrate top-k — still on-device, replicated over model
        all_scores = jax.lax.all_gather(scores, model_axis)   # [S, b, k]
        all_sids = jax.lax.all_gather(gsids, model_axis)
        return merge_shard_topk(all_scores, all_sids, k, sub)

    return run(stacked, qs, qlens)


class ShardedCompletionIndex:
    """Host-facing wrapper: build shards, stack, serve over a mesh.

    Shards share one :class:`IndexSpec`; ``save``/``load`` persist every
    shard's npz container so a serving process restarts without rebuilding
    any sub-trie.
    """

    def __init__(self, strings, scores, rules, *, mesh=None, n_shards=None,
                 kind=None, model_axis="model", data_axes=("data",),
                 spec=None, **build_kwargs):
        if spec is None:
            spec = IndexSpec(kind=kind or "et", **build_kwargs)
        elif kind is not None or build_kwargs:
            raise TypeError("pass either spec= or IndexSpec kwargs, not both")
        spec.validate_sharded()   # before any shard is built, not after
        if mesh is not None:
            n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[model_axis]
        elif n_shards is None:
            raise TypeError(
                "pass mesh= (device-sharded serving) or n_shards= "
                "(single-process local mode)")
        buckets = shard_strings(strings, scores, n_shards)
        shards = [
            build_index(b[0] if b[0] else [""], b[1] if b[1] else [1],
                        rules, spec=spec)
            for b in buckets
        ]
        self._init_from_shards(shards, mesh=mesh, model_axis=model_axis,
                               data_axes=data_axes, spec=spec)

    def _init_from_shards(self, shards, *, mesh, model_axis, data_axes,
                          spec):
        self.mesh = mesh
        self.model_axis = model_axis
        self.data_axes = data_axes
        # fail unsupported-on-sharded spec combinations (packed layout)
        # here, with the workaround in the message, instead of deep in
        # stack_shards — every construction path funnels through this
        self.spec = spec.validate_sharded()
        self.shards = shards
        stacked, self.cfg, self.stride = stack_shards(self.shards)
        if mesh is not None:
            sharding = NamedSharding(mesh, P(model_axis))
            put = lambda x: jax.device_put(x, sharding)
        else:
            put = jnp.asarray  # local mode: whole stacked trie on one device
        self.device_tries = jax.tree.map(
            put, stacked, is_leaf=lambda x: isinstance(x, np.ndarray))
        self._compile_cache = CompileCache(maxsize=16)

    @classmethod
    def from_shards(cls, shards, *, mesh=None, model_axis="model",
                    data_axes=("data",), spec=None):
        """Wrap already-built per-shard indexes (skips construction)."""
        self = cls.__new__(cls)
        self._init_from_shards(shards, mesh=mesh, model_axis=model_axis,
                               data_axes=data_axes,
                               spec=spec or shards[0].spec)
        return self

    # -- persistence -------------------------------------------------------

    def save(self, path: str) -> None:
        """Write a directory: meta.json + one npz container per shard."""
        os.makedirs(path, exist_ok=True)
        for i, shard in enumerate(self.shards):
            shard.save(os.path.join(path, f"shard_{i:04d}.npz"))
        meta = {"format_version": 1, "n_shards": len(self.shards),
                "spec": self.spec.to_dict()}
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)

    @classmethod
    def load(cls, path: str, *, mesh=None, model_axis="model",
             data_axes=("data",)) -> "ShardedCompletionIndex":
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        n_shards = meta["n_shards"]
        if mesh is not None:
            mesh_shards = dict(zip(mesh.axis_names,
                                   mesh.devices.shape))[model_axis]
            if n_shards != mesh_shards:
                raise ValueError(
                    f"saved index has {n_shards} shards but mesh axis "
                    f"{model_axis!r} has {mesh_shards} devices")
        shards = [CompletionIndex.load(os.path.join(path, f"shard_{i:04d}.npz"))
                  for i in range(n_shards)]
        return cls.from_shards(shards, mesh=mesh, model_axis=model_axis,
                               data_axes=data_axes,
                               spec=IndexSpec.from_dict(meta["spec"]))

    def lookup_string(self, gsid: int) -> str:
        shard, sid = divmod(int(gsid), self.stride)
        return self.shards[shard].strings[sid].decode("utf-8", errors="replace")

    def _local_fn(self, B: int, L: int, k: int):
        """Jitted single-process answer: loop the static shard count over
        the stacked trie, rebase sids to global ids, fuse the merge — one
        dispatch per (bucketed) batch shape, LRU-cached."""
        key = (B, L, k, self.cfg)

        def factory():
            cfg, stride, S = self.cfg, self.stride, len(self.shards)
            sub = eng.get_substrate(cfg.substrate)

            def run(trie, qs, qlens):
                per_s, per_i = [], []
                for s in range(S):
                    local = jax.tree.map(lambda x: x[s], trie)
                    scores, sids, _ = eng.complete_batch(
                        local, cfg, qs, qlens, k, sub)
                    per_s.append(scores)
                    per_i.append(jnp.where(sids >= 0, sids + s * stride, -1))
                return merge_shard_topk(
                    jnp.stack(per_s), jnp.stack(per_i), k, sub)

            return jax.jit(run)

        return self._compile_cache.get(key, factory)

    def _mesh_fn(self, B: int, L: int, k: int):
        """Jitted :func:`sharded_complete` over this index's mesh, one
        executable per (batch, length, k), LRU-cached like local mode."""
        key = ("mesh", B, L, k, self.cfg)

        def factory():
            cfg, mesh, stride = self.cfg, self.mesh, self.stride
            return jax.jit(lambda trie, qs, qlens: sharded_complete(
                trie, cfg, qs, qlens, k, mesh=mesh, sid_stride=stride,
                data_axes=self.data_axes, model_axis=self.model_axis))

        return self._compile_cache.get(key, factory)

    def _complete_local(self, qs: np.ndarray, qlens: np.ndarray, k: int,
                        n_real: int):
        """Answer a padded query batch without a mesh (see module docstring);
        batch is bucketed up to a power of two so shapes re-hit the cache."""
        B = bucket_size(n_real)
        qs_p = np.zeros((B, qs.shape[1]), np.int32)
        qlens_p = np.zeros((B,), np.int32)
        qs_p[:n_real], qlens_p[:n_real] = qs, qlens
        fn = self._local_fn(B, qs.shape[1], k)
        scores, gsids = fn(self.device_tries, jnp.asarray(qs_p),
                           jnp.asarray(qlens_p))
        return scores[:n_real], gsids[:n_real]

    def complete(self, queries, k: int = 10):
        from repro.core.alphabet import pad_queries

        max_len = max((len(q) for q in queries), default=1)
        L = max(8, 1 << (max_len - 1).bit_length())
        qs, qlens = pad_queries(queries, L)
        if self.mesh is not None:
            fn = self._mesh_fn(qs.shape[0], qs.shape[1], k)
            scores, gsids = fn(self.device_tries, jnp.asarray(qs),
                               jnp.asarray(qlens))
        else:
            scores, gsids = self._complete_local(
                np.asarray(qs), np.asarray(qlens), k, len(queries))
        scores, gsids = np.asarray(scores), np.asarray(gsids)
        out = []
        for b in range(len(queries)):
            row = [(int(s), self.lookup_string(g))
                   for s, g in zip(scores[b], gsids[b]) if s >= 0 and g >= 0]
            out.append(row)
        return out

    def session(self, k: int = 10):
        raise UnsupportedOnShardedIndex(
            "ShardedCompletionIndex has no per-keystroke session: a "
            "resumable locus frontier would have to live on every shard "
            "and merge per keystroke — use complete() for batch lookups, "
            "or a local CompletionIndex for incremental typing")

    def open_session(self, k: int = 10):
        raise UnsupportedOnShardedIndex(
            "ShardedCompletionIndex has no per-keystroke session: a "
            "resumable locus frontier would have to live on every shard "
            "and merge per keystroke — use complete() for batch lookups, "
            "or a local CompletionIndex for incremental typing")
