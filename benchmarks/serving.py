"""B10: serving layer — per-session sequential dispatch vs the
continuous-batching keystroke scheduler, under a Zipf multi-session load.

Replays one interleaved multi-session keystroke stream
(:func:`repro.data.strings.make_keystroke_events`) twice through the same
index: once with every keystroke paying its own device dispatch
(stateful :class:`~repro.api.session.Session` per typist) and once
through the :class:`~repro.serving.scheduler.KeystrokeScheduler`'s
coalesced micro-batches.  Demuxed per-keystroke results are checked
bit-identical; both rows land in the perf trajectory
(``BENCH_substrates.json``) so the batched path's us/keystroke is gated
against its own history like the kernel rows.

Timing takes the best of ``repeats`` full replays per path (the
sequential path's thousands of tiny dispatches are noisy on shared CI
machines; the tail percentiles come from the last repeat's stats).

  PYTHONPATH=src python -m benchmarks.serving               # table
  PYTHONPATH=src python -m benchmarks.serving --smoke \
      --out serving-smoke.json                              # CI artifact
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from benchmarks.common import build_index, dataset, emit
from repro.api.compile_cache import enable_persistent_cache
from repro.data.strings import make_keystroke_events
from repro.launch.serve import _replay_batched, _replay_sequential
from repro.serving import BatchStats, CompletionService


def _bench_overlay(ds, events, sessions: int, repeats: int):
    """us/keystroke with a pending mutation batch on the index.

    Every keystroke answers through the overlay-merged one-shot path
    (base over-fetch + side-index + fused rank merge), so this row prices
    the mutated steady state between compactions; the serving_seq /
    serving_batch rows price the unmutated hot path (their per-keystroke
    ``has_mutations`` check is the only overlay cost they carry)."""
    from benchmarks.common import build_index as build
    from repro.launch.serve import _replay_sequential

    idx = build(ds, "et", cache_k=10)
    base = idx.strings   # sorted unique bytes, unlike the raw dataset
    for i in range(32):
        idx.insert(b"zz~overlay-%d" % i, 100 + i)
    for s in base[:32:2]:
        idx.delete(s)
    for s in base[1:33:2]:
        idx.update_score(s, 7)
    # the merged path re-dispatches one-shot per keystroke; a slice of
    # the stream keeps the row's wall cost in smoke range while still
    # touching every prefix-length bucket
    events = events[:max(len(events) // 4, 1)]
    svc = CompletionService(idx)
    _replay_sequential(svc, events, sessions)   # compile/warmup
    best = float("inf")
    for _ in range(repeats):
        svc.stats.reset_keystrokes()
        t0 = time.perf_counter()
        out = _replay_sequential(svc, events, sessions)
        best = min(best, time.perf_counter() - t0)
    return idx, svc, len(out), best


def bench_serving(smoke: bool = False, sessions: int = 16, block: int = 16,
                  repeats: int = 3):
    """Returns three trajectory rows: serving_seq, serving_batch and
    serving_overlay (the mutated steady state)."""
    ds = dataset("dblp")
    if smoke:
        ds = type(ds)(name=ds.name, strings=ds.strings[:2000],
                      scores=ds.scores[:2000], rules=ds.rules)
    # long enough streams that the startup ramp and final drain (which
    # run below full occupancy) are a small share of the replay
    n_queries = 128 if smoke else 512
    idx = build_index(ds, "et", cache_k=10)
    events = make_keystroke_events(ds, sessions, n_queries, seed=1)

    seq = CompletionService(idx)
    bat = CompletionService(idx, batching=True, block=block,
                            max_wait_ms=100.0, max_queue=16 * block)
    # one untimed replay per path compiles every jit shape both will hit
    seq_results = _replay_sequential(seq, events, sessions)
    bat_results = _replay_batched(bat, events, sessions)
    assert seq_results == bat_results, \
        "batched demux diverged from sequential replay"
    n = len(seq_results)

    def timed_once(svc, replay):
        svc.stats.reset_keystrokes()
        if svc.batching:
            svc.scheduler.stats = BatchStats()
        t0 = time.perf_counter()
        replay(svc, events, sessions)
        return time.perf_counter() - t0

    # interleave the repeats so ambient machine drift hits both paths
    # alike instead of biasing whichever ran second
    seq_s = bat_s = float("inf")
    for _ in range(repeats):
        seq_s = min(seq_s, timed_once(seq, _replay_sequential))
        bat_s = min(bat_s, timed_once(bat, _replay_batched))
    bstats = bat.scheduler.stats

    ov_idx, ov_svc, ov_n, ov_s = _bench_overlay(ds, events, sessions,
                                                repeats)

    base = {
        "kind": idx.kind,
        "substrate": idx.substrate,
        "backend": jax.default_backend(),
        "interpret_mode": False,
        "fused_walk": False, "fused_beam": False,
        "streamed_walk": False, "streamed_beam": False,
        "compression": idx.compression,
        "memory_budget": idx.memory_budget,
        "bytes_per_string": round(idx.stats.bytes_per_string, 1),
        "sessions": sessions, "keystrokes": n,
    }
    return [
        dict(base, engine="serving_seq",
             us_per_q=round(seq_s / max(n, 1) * 1e6, 1),
             p50_ms=round(seq.stats.p50_keystroke_ms(), 3),
             p99_ms=round(seq.stats.p99_keystroke_ms(), 3)),
        dict(base, engine="serving_batch", block=block,
             us_per_q=round(bat_s / max(n, 1) * 1e6, 1),
             p50_ms=round(bat.stats.p50_keystroke_ms(), 3),
             p99_ms=round(bat.stats.p99_keystroke_ms(), 3),
             mean_occupancy=round(bstats.mean_occupancy, 2),
             speedup_vs_seq=round(seq_s / max(bat_s, 1e-9), 2)),
        dict(base, engine="serving_overlay",
             keystrokes=ov_n,
             overlay_backlog=ov_idx.mutation_backlog,
             us_per_q=round(ov_s / max(ov_n, 1) * 1e6, 1),
             p50_ms=round(ov_svc.stats.p50_keystroke_ms(), 3),
             p99_ms=round(ov_svc.stats.p99_keystroke_ms(), 3)),
    ]


def _table(rows):
    emit([[r["engine"], r["kind"], r["substrate"], r["us_per_q"],
           r["p50_ms"], r["p99_ms"], r.get("speedup_vs_seq", "-")]
          for r in rows],
         ["engine", "kind", "substrate", "us_per_keystroke", "p50_ms",
          "p99_ms", "speedup"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI; pairs with --out for the "
                         "perf-trajectory artifact")
    ap.add_argument("--out", default=None,
                    help="also write rows as JSON to this path")
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    enable_persistent_cache()

    rows = bench_serving(smoke=args.smoke, sessions=args.sessions,
                         block=args.block, repeats=args.repeats)
    _table(rows)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"benchmark": "serving",
                       "backend": jax.default_backend(),
                       "smoke": args.smoke, "rows": rows}, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
