"""Serving launcher.

  python -m repro.launch.serve --arch autocomplete-usps --queries 1000
  python -m repro.launch.serve --arch autocomplete-usps --workload keystroke
  python -m repro.launch.serve --arch qwen2.5-14b --smoke   (LM decode)

For autocomplete archs this is the paper's end-to-end system: build (or
``--load-index``) the index, replay a workload — one-shot batches or an
incremental per-keystroke stream through stateful sessions — and report
latency/throughput (Fig. 7-style numbers).  ``--substrate`` picks the
execution substrate (jnp reference vs Pallas kernels; ``auto`` resolves
to jnp, the only substrate that compiles for a TPU today).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.api import IndexSpec, build_index
from repro.api.compile_cache import enable_persistent_cache
from repro.core import CompletionIndex, make_rules
from repro.configs import all_archs
from repro.data.strings import DATASETS, make_keystroke_events, make_workload
from repro.serving import (BatchStats, CompletionService, LMServer, Request,
                           SchedulerOverloaded)


def _make_index(spec, args):
    """Build the index from the arch's dataset, or restore a saved one."""
    name = spec.arch_id.split("-")[-1]
    cfg = spec.make_config()
    n = min(cfg.n_strings, args.n_strings)
    ds = DATASETS[name](n=n, seed=0)
    t0 = time.perf_counter()
    if args.load_index:
        # loading re-resolves the *saved* spec's substrate for this host;
        # only an explicit --substrate flag overrides it
        idx = CompletionIndex.load(args.load_index)
        overrides = {}
        if args.substrate is not None:
            overrides["substrate"] = args.substrate
        if args.memory_budget is not None:
            overrides["memory_budget"] = args.memory_budget
        if overrides:
            idx.reconfigure(**overrides)
    else:
        idx = build_index(
            ds.strings, ds.scores, make_rules(ds.rules),
            IndexSpec(kind=args.index_kind, cache_k=args.cache_k,
                      substrate=args.substrate or "auto",
                      memory_budget=args.memory_budget or 0,
                      compression=args.compression))
    build_s = time.perf_counter() - t0
    if args.save_index:
        idx.save(args.save_index)
    return ds, idx, build_s


def serve_autocomplete(spec, args):
    ds, idx, build_s = _make_index(spec, args)
    svc = CompletionService(idx)
    queries = make_workload(ds, args.queries, seed=1)
    # warmup + timed batches
    svc.complete(queries[:32], k=10)
    t0 = time.perf_counter()
    bs = args.batch
    results = []
    for i in range(0, len(queries), bs):
        results.extend(svc.complete(queries[i : i + bs], k=10))
    dt = time.perf_counter() - t0
    hit = sum(bool(r) for r in results) / max(len(results), 1)
    out = {
        "arch": spec.arch_id, "kind": idx.kind,
        "substrate": idx.substrate,
        "compression": idx.compression,
        "memory_budget": idx.memory_budget,
        "workload": "batch",
        "n_strings": idx.stats.n_strings,
        "bytes_per_string": round(idx.stats.bytes_per_string, 1),
        "build_seconds": round(build_s, 2),
        "queries": len(results),
        "us_per_completion": round(dt / max(len(results), 1) * 1e6, 1),
        "hit_rate": round(hit, 3),
        "retries_per_1k": round(idx.lookups.retries_per_1k, 3),
    }
    print(json.dumps(out))
    return out


def serve_keystroke(spec, args):
    """Incremental replay: every query is typed char-by-char through a
    stateful session, i.e. the per-keystroke serving contract."""
    ds, idx, build_s = _make_index(spec, args)
    svc = CompletionService(idx)
    queries = make_workload(ds, args.queries, seed=1)
    sess = svc.open_session(k=10)
    sess.type(queries[0])                         # compile/warmup
    svc.stats.reset_keystrokes()
    hits = 0
    for q in queries:
        sess.reset()
        rows = sess.type(q)
        hits += bool(rows)
    st = svc.stats
    out = {
        "arch": spec.arch_id, "kind": idx.kind,
        "substrate": idx.substrate,
        "compression": idx.compression,
        "memory_budget": idx.memory_budget,
        "workload": "keystroke",
        "n_strings": idx.stats.n_strings,
        "build_seconds": round(build_s, 2),
        "queries": len(queries),
        "keystrokes": st.n_keystrokes,
        "us_per_keystroke": round(st.mean_keystroke_ms * 1e3, 1),
        "p99_keystroke_ms": round(st.p99_keystroke_ms(), 3),
        "hit_rate": round(hits / max(len(queries), 1), 3),
    }
    print(json.dumps(out))
    return out


def _replay_sequential(svc, events, n_sessions, k=10):
    """One device dispatch per keystroke: the pre-batching serving shape."""
    sessions = [svc.open_session(k=k) for _ in range(n_sessions)]
    out = []
    for s, c in events:
        if c < 0:
            sessions[s].reset()
        else:
            out.append(sessions[s].type(bytes([c])))
    for sess in sessions:
        sess.close()
    return out


def _replay_batched(svc, events, n_sessions, k=10):
    """Keystrokes submitted non-blocking so concurrent sessions coalesce
    into shared micro-batches.  Backpressure sheds load with one forced
    flush; a session whose stream ends is closed immediately so its idle
    lane stops holding back the full-flush condition."""
    remaining = [0] * n_sessions
    for s, _ in events:
        remaining[s] += 1
    sessions = [svc.open_session(k=k) for _ in range(n_sessions)]
    tickets = []
    for s, c in events:
        if c < 0:
            sessions[s].reset()
        else:
            try:
                tickets.append(sessions[s].submit(c))
            except SchedulerOverloaded:
                svc.flush()
                tickets.append(sessions[s].submit(c))
        remaining[s] -= 1
        if remaining[s] == 0:
            sessions[s].close()
    svc.drain()
    return [t.results for t in tickets]


def serve_zipf(spec, args):
    """Multi-session Zipf keystroke load, sequential per-session dispatch
    vs the continuous-batching scheduler — same events, bit-identity
    checked, speedup and tail latency reported."""
    ds, idx, build_s = _make_index(spec, args)
    events = make_keystroke_events(ds, args.sessions, args.queries, seed=1)
    seq = CompletionService(idx)
    bat = CompletionService(idx, batching=True, block=args.block,
                            max_wait_ms=args.max_wait_ms,
                            max_queue=args.max_queue)

    # untimed warmup replay through both paths so every jit shape (session
    # fns, slab fns, fallback buckets) is compiled before timing
    seq_results = _replay_sequential(seq, events, args.sessions)
    bat_results = _replay_batched(bat, events, args.sessions)

    def timed(svc, replay):
        svc.stats.reset_keystrokes()
        if svc.batching:
            svc.scheduler.stats = BatchStats()
        t0 = time.perf_counter()
        replay(svc, events, args.sessions)
        return time.perf_counter() - t0

    # best-of with the repeats interleaved, so ambient machine drift hits
    # both paths alike instead of biasing whichever ran second (the
    # sequential path's thousands of tiny dispatches are the noisy one)
    seq_dt = bat_dt = float("inf")
    for _ in range(args.repeats):
        seq_dt = min(seq_dt, timed(seq, _replay_sequential))
        bat_dt = min(bat_dt, timed(bat, _replay_batched))

    n = len(seq_results)
    bstats = bat.scheduler.stats
    out = {
        "arch": spec.arch_id, "kind": idx.kind,
        "substrate": idx.substrate,
        "compression": idx.compression,
        "workload": "zipf",
        "n_strings": idx.stats.n_strings,
        "build_seconds": round(build_s, 2),
        "sessions": args.sessions, "block": args.block,
        "queries": args.queries, "keystrokes": n,
        "bit_identical": seq_results == bat_results,
        "seq_us_per_keystroke": round(seq_dt / max(n, 1) * 1e6, 1),
        "batch_us_per_keystroke": round(bat_dt / max(n, 1) * 1e6, 1),
        "speedup": round(seq_dt / max(bat_dt, 1e-9), 2),
        "seq_p50_ms": round(seq.stats.p50_keystroke_ms(), 3),
        "seq_p99_ms": round(seq.stats.p99_keystroke_ms(), 3),
        "batch_p50_ms": round(bat.stats.p50_keystroke_ms(), 3),
        "batch_p99_ms": round(bat.stats.p99_keystroke_ms(), 3),
        "flushes": bstats.n_flushes,
        "mean_occupancy": round(bstats.mean_occupancy, 2),
        "deadline_flushes": bstats.deadline_flushes,
        "fallbacks": bstats.fallbacks,
        "queue_wait_ms": round(bstats.mean_queue_wait_ms, 3),
        "answer_lag_ms": round(bstats.mean_answer_lag_ms, 3),
    }
    print(json.dumps(out))
    return out


def serve_churn(spec, args):
    """Zipf keystroke stream with the dictionary churning underneath it.

    Every ``--churn-every`` keystrokes a mutation batch lands on the live
    index (one trending insert, one delete, one re-score); once the
    overlay backlog reaches ``--compact-at`` the service compacts —
    rebuilding in the background shape and hot-swapping under the open
    scheduler lanes, which migrate at their next flush.  Reports
    keystroke throughput alongside mutation/compaction cost, and
    verifies zero lost keystrokes plus probe-query agreement between the
    final overlay-merged answers and the post-compaction rebuilt index.
    """
    ds, idx, build_s = _make_index(spec, args)
    events = make_keystroke_events(ds, args.sessions, args.queries, seed=1)
    svc = CompletionService(idx, batching=True, block=args.block,
                            max_wait_ms=args.max_wait_ms,
                            max_queue=args.max_queue)
    rng = np.random.default_rng(2)
    base_strings = list(idx.strings)
    deleted: set[bytes] = set()
    remaining = [0] * args.sessions
    for s, _ in events:
        remaining[s] += 1
    sessions = [svc.open_session(k=10) for _ in range(args.sessions)]
    tickets = []
    mutations = {"insert": 0, "delete": 0, "rescore": 0}
    compactions = n_hot = 0
    mut_s = compact_s = 0.0
    t0 = time.perf_counter()
    for i, (s, c) in enumerate(events):
        if i and i % args.churn_every == 0:
            m0 = time.perf_counter()
            idx.insert(b"zz~trending-%d" % n_hot,
                       int(rng.integers(1, 1000)))
            n_hot += 1
            mutations["insert"] += 1
            victim = base_strings[int(rng.integers(len(base_strings)))]
            if victim not in deleted:
                idx.delete(victim)
                deleted.add(victim)
                mutations["delete"] += 1
            target = base_strings[int(rng.integers(len(base_strings)))]
            if target not in deleted:
                idx.update_score(target, int(rng.integers(1, 1000)))
                mutations["rescore"] += 1
            mut_s += time.perf_counter() - m0
            if idx.mutation_backlog >= args.compact_at:
                c0 = time.perf_counter()
                svc.compact()
                compact_s += time.perf_counter() - c0
                compactions += 1
        if c < 0:
            sessions[s].reset()
        else:
            try:
                tickets.append(sessions[s].submit(c))
            except SchedulerOverloaded:
                svc.flush()
                tickets.append(sessions[s].submit(c))
        remaining[s] -= 1
        if remaining[s] == 0:
            sessions[s].close()
    svc.drain()
    dt = time.perf_counter() - t0
    lost = sum(t.results is None for t in tickets)
    # verification: the overlay-merged answers must survive the fold —
    # compact() rebuilds from scratch internally, so pre/post agreement
    # on a probe batch is a merged-path-vs-rebuild differential for free
    probe = sorted({bytes(t.prefix)[:3] for t in tickets})[:24]
    pre = idx.complete(probe, k=10)
    c0 = time.perf_counter()
    svc.compact()
    compact_s += time.perf_counter() - c0
    compactions += 1
    post = idx.complete(probe, k=10)
    bstats = svc.scheduler.stats
    n = len(tickets)
    out = {
        "arch": spec.arch_id, "kind": idx.kind,
        "substrate": idx.substrate,
        "compression": idx.compression,
        "workload": "churn",
        "n_strings": idx.stats.n_strings,
        "build_seconds": round(build_s, 2),
        "sessions": args.sessions, "block": args.block,
        "keystrokes": n,
        "us_per_keystroke": round(dt / max(n, 1) * 1e6, 1),
        "p50_ms": round(svc.stats.p50_keystroke_ms(), 3),
        "p99_ms": round(svc.stats.p99_keystroke_ms(), 3),
        "mutations": mutations,
        "mutation_ms_mean": round(
            mut_s / max(sum(mutations.values()), 1) * 1e3, 3),
        "compactions": compactions,
        "compact_ms_mean": round(compact_s / max(compactions, 1) * 1e3, 1),
        "migrations": bstats.migrations,
        "final_epoch": idx.epoch,
        "lost_keystrokes": lost,
        "verified": pre == post,
        "flushes": bstats.n_flushes,
        "mean_occupancy": round(bstats.mean_occupancy, 2),
    }
    print(json.dumps(out))
    return out


def serve_lm(spec, args):
    from repro.models import transformer as tf

    cfg = spec.make_smoke_config()
    params, _ = tf.init_lm(jax.random.PRNGKey(0), cfg)
    server = LMServer(params, cfg, n_slots=args.batch, max_len=96)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.queries):
        server.scheduler.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, 8 + i % 8),
            max_new_tokens=16))
    done = server.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in done)
    ttfts = [r.first_token_at - r.created for r in done]
    out = {
        "arch": spec.arch_id, "requests": len(done),
        "tokens": toks, "tok_per_s": round(toks / dt, 1),
        "mean_ttft_ms": round(float(np.mean(ttfts)) * 1e3, 1),
    }
    print(json.dumps(out))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--n-strings", type=int, default=100_000)
    ap.add_argument("--index-kind", default="et",
                    choices=["tt", "et", "ht", "plain"])
    ap.add_argument("--cache-k", type=int, default=0)
    ap.add_argument("--compression", default="none",
                    choices=["none", "packed"],
                    help="on-device index layout; packed = format-v4 "
                         "compressed tables (narrow dtypes, elided "
                         "planes, collapsed unary chains). Ignored with "
                         "--load-index (the container records it)")
    ap.add_argument("--substrate", default=None,
                    choices=["jnp", "pallas", "auto"],
                    help="execution substrate; auto = jnp on every "
                         "backend (pallas is opt-in: interpret mode "
                         "off-TPU, not yet compilable on a TPU). "
                         "Default: auto when building, the saved choice "
                         "when --load-index")
    ap.add_argument("--memory-budget", type=int, default=None,
                    help="VMEM bytes the pallas substrate may spend "
                         "keeping tables resident; larger tables stream "
                         "from HBM (0/unset = substrate default). Applies "
                         "to built and --load-index'd indexes, batch and "
                         "keystroke workloads alike")
    ap.add_argument("--workload", default="batch",
                    choices=["batch", "keystroke", "zipf", "churn"],
                    help="batch = one-shot query batches; keystroke = one "
                         "session typing char-by-char; zipf = many "
                         "concurrent sessions under Zipf-skewed traffic, "
                         "sequential vs continuous-batching comparison; "
                         "churn = zipf traffic with live insert/delete/"
                         "re-score batches and periodic compaction "
                         "hot-swaps under the open sessions")
    ap.add_argument("--churn-every", type=int, default=64,
                    help="keystrokes between mutation batches for "
                         "--workload churn")
    ap.add_argument("--compact-at", type=int, default=48,
                    help="overlay backlog (pending inserts+tombstones) "
                         "that triggers a compaction hot-swap for "
                         "--workload churn")
    ap.add_argument("--sessions", type=int, default=8,
                    help="concurrent typing sessions for --workload zipf")
    ap.add_argument("--block", type=int, default=8,
                    help="scheduler micro-batch lanes (the slab jit shape)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="scheduler latency budget before a partial-block "
                         "deadline flush")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="scheduler admission-queue bound (default "
                         "4*block); deeper queues trade keystroke latency "
                         "for fuller micro-batches")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed replays per path for --workload zipf "
                         "(interleaved best-of)")
    ap.add_argument("--save-index", default=None,
                    help="persist the built index to this .npz path")
    ap.add_argument("--load-index", default=None,
                    help="restore an index instead of rebuilding")
    ap.add_argument("--smoke", action="store_true", default=True)
    args = ap.parse_args()
    enable_persistent_cache()

    spec = all_archs()[args.arch]
    if spec.family == "autocomplete":
        if args.workload == "keystroke":
            serve_keystroke(spec, args)
        elif args.workload == "zipf":
            serve_zipf(spec, args)
        elif args.workload == "churn":
            serve_churn(spec, args)
        else:
            serve_autocomplete(spec, args)
    elif spec.family == "lm":
        serve_lm(spec, args)
    else:
        raise SystemExit(f"no serve mode for family {spec.family}")


if __name__ == "__main__":
    main()
