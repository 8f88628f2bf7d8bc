"""B9: execution substrates — jnp vs pallas, end-to-end on the B7 workload.

Times ``CompletionIndex.complete`` through both registered substrates on
the same built indexes (the substrate switch is a config flip; host/device
structures are shared), across two axes:

- *phase-2 engine* (from B7): the paper-faithful beam vs the beyond-paper
  cached top-K (``cached_k16``), on the ET index;
- *rule-bearing walk* (the fused locus-DP kernel's workload): tt/et/ht
  with the dataset's synonym rule set, where phase 1 is the synonym-aware
  frontier sweep rather than the rule-free prefix walk;
- *beam phase 2* (the fused beam kernel's workload): every ``beam`` row
  runs the generator-pool priority search, and the rule-free ``plain``
  row isolates it behind the trivial prefix walk.  Each row records
  whether the pallas substrate claimed the beam natively (``fused_beam``,
  from the ``can_beam_batch`` probe);
- *DMA-streamed tier*: two pallas-only rows re-run the plain and ht beam
  workloads under a VMEM budget that evicts the dictionary-sized tables,
  so phase 1 and phase 2 go through the HBM-streaming kernels
  (``streamed_walk``/``streamed_beam`` columns, from the
  ``walk_variant``/``beam_variant`` probes).  Off-TPU these measure the
  interpret-mode emulation of the DMA pipeline, not real overlap;
- *compressed layout* (format v4): packed twins of the ET rows record
  the bytes/string drop (``compression``/``bytes_per_string`` columns),
  and a fixed-budget pair shows the tier flip — at the same
  ``FLIP_BUDGET`` the uncompressed ET index runs DMA-streamed while the
  packed layout fits VMEM-resident.

On CPU the pallas column runs the kernels in interpret mode — that
measures dispatch correctness and overhead, not kernel speed; the TPU run
is where the comparison is meaningful (see README "choosing a
substrate").  Each row records whether the pallas substrate claimed the
walk natively (``fused_walk``, from the ``can_walk_batch`` probe).

  PYTHONPATH=src python -m benchmarks.substrates            # table
  PYTHONPATH=src python -m benchmarks.substrates --smoke \
      --out substrates-smoke.json                            # CI artifact
"""

from __future__ import annotations

import argparse
import json

import jax

from benchmarks.common import (SIZES, build_index, dataset, emit,
                               fixed_batches, time_batches)
from repro.api.compile_cache import enable_persistent_cache
from repro.data.strings import make_workload

# a fixed VMEM budget sized between the packed and uncompressed resident
# footprints of the smoke-scale ET index: at the same budget the
# uncompressed layout is forced onto the DMA-streamed tier while the
# packed (format v4) layout fits VMEM-resident — the tier flip the
# compressed layout exists to buy
FLIP_BUDGET = 1 << 20

# (label, index kind, build kwargs, streamed, compression, budget) — the
# two phase-2 engines benchmarked in B7 on ET, the rule-bearing walk
# workloads for the fused locus-DP kernel (tt = link store, ht = links +
# teleports), a rule-free beam row where phase 1 is the trivial prefix
# walk so the beam phase-2 kernel dominates the measurement, two
# DMA-streamed-tier rows (the same workloads under a VMEM budget that
# evicts every dictionary-sized table, so the HBM streaming path is what
# gets timed), compressed (format v4) twins of the ET rows, and the
# fixed-budget flip pair described at FLIP_BUDGET above
CASES = [
    ("beam", "et", {}, False, "none", None),
    ("cached_k16", "et", {"cache_k": 16}, False, "none", None),
    ("beam", "tt", {}, False, "none", None),
    ("beam", "ht", {}, False, "none", None),
    ("beam", "plain", {}, False, "none", None),
    ("beam", "plain", {}, True, "none", None),
    ("beam", "ht", {}, True, "none", None),
    ("beam", "et", {}, False, "packed", None),
    ("cached_k16", "et", {"cache_k": 16}, False, "packed", None),
    ("beam", "et", {}, False, "none", FLIP_BUDGET),
    ("beam", "et", {}, False, "packed", FLIP_BUDGET),
    # query-mode rows: the typo-tolerant walk (edit_budget=1 widens the
    # frontier sweep with substitute/insert/delete transitions) and the
    # multi-term index (build-time token-skip rule synthesis; the beam
    # phase is unchanged, the walk consumes the synthesized teleports)
    ("edit1_walk", "et", {"edit_budget": 1}, False, "none", None),
    ("multiterm_beam", "multiterm", {}, False, "none", None),
]
SUBSTRATES = ("jnp", "pallas")


def _streamed_budget(idx):
    """A VMEM budget that forces the DMA-streamed tier: room for the
    rule trie (the streamed locus kernel keeps it resident) but for none
    of the dictionary-sized tables."""
    from repro.core import engine as eng

    return eng.get_substrate("pallas").min_streamed_budget(idx.device)


def bench_substrates(k: int = 10, batch: int = 256, name: str = "usps",
                     smoke: bool = False):
    """Returns one row dict per (engine, kind, substrate) with us/query."""
    from repro.core import engine as eng

    n_queries = 200 if smoke else SIZES["queries"] // 2
    ds = dataset(name)
    if smoke:
        ds = type(ds)(name=ds.name, strings=ds.strings[:2000],
                      scores=ds.scores[:2000], rules=ds.rules)
    qs = make_workload(ds, n_queries, seed=11, max_len=14)
    if smoke:
        batch = 64
    rows = []
    # the probe must see the padded length complete() will actually jit
    # with, or the fused_walk column could misreport the timed path
    from repro.api.compile_cache import bucket_size
    seq_len = bucket_size(max(len(q) for q in qs))
    for engine, kind, kw, streamed, compression, budget in CASES:
        idx = build_index(ds, kind, compression=compression, **kw)
        if streamed:
            idx.reconfigure(memory_budget=_streamed_budget(idx))
        elif budget is not None:
            idx.reconfigure(memory_budget=budget)
        # streamed and fixed-budget rows only make sense on the pallas
        # substrate (the jnp reference ignores the VMEM budget) — the
        # resident cases keep the jnp twin as the reference column
        for substrate in (SUBSTRATES if not streamed and budget is None
                          else ("pallas",)):
            idx.reconfigure(substrate=substrate)
            sub = eng.get_substrate(substrate)
            walk_v = sub.walk_variant(idx.device, idx.cfg, seq_len) \
                if substrate == "pallas" else None
            beam_v = sub.beam_variant(idx.device, idx.cfg, k) \
                if substrate == "pallas" and engine == "beam" else None
            batches = fixed_batches(qs, batch)
            sec = time_batches(lambda b: idx.complete(b, k=k), batches)
            rows.append({
                "engine": engine,
                "kind": kind,
                "substrate": substrate,
                "backend": jax.default_backend(),
                "interpret_mode": jax.default_backend() != "tpu"
                and substrate == "pallas",
                "fused_walk": walk_v is not None,
                "fused_beam": beam_v is not None,
                "streamed_walk": walk_v == "streamed",
                "streamed_beam": beam_v == "streamed",
                "compression": compression,
                "memory_budget": idx.memory_budget,
                "bytes_per_string": round(idx.stats.bytes_per_string, 1),
                "us_per_q": round(sec * 1e6, 1),
            })
    return rows


def _table(rows):
    emit([[r["engine"], r["kind"], r["substrate"], r["compression"],
           r["bytes_per_string"], r["us_per_q"]]
          for r in rows],
         ["engine", "kind", "substrate", "compression", "bytes_per_string",
          "us_per_q"])


def b9_substrates():
    rows = bench_substrates()
    _table(rows)
    return rows


ALL = {
    "b9": ("execution substrates: jnp vs pallas end-to-end", b9_substrates),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI; pairs with --out for the "
                         "perf-trajectory artifact")
    ap.add_argument("--out", default=None,
                    help="also write rows as JSON to this path")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args()
    enable_persistent_cache()

    rows = bench_substrates(k=args.k, batch=args.batch, smoke=args.smoke)
    _table(rows)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"benchmark": "substrates",
                       "backend": jax.default_backend(),
                       "smoke": args.smoke, "rows": rows}, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
