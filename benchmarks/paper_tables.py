"""The paper's tables/figures, one function each (deliverable d).

B1 Table 1: dataset characteristics
B2 Table 2 + Fig 5: structure size (bytes/string) incl. BL baseline + breakdown
B3 Fig 6: construction time
B4 Fig 7: top-10 lookup time vs query length (TT/ET/HT)
B5 Fig 8: HT lookup time vs alpha
B6 Fig 9: size + lookup time vs #strings (scalability)

Run: PYTHONPATH=src python -m benchmarks.run [--only b4]
Scales with REPRO_BENCH_SCALE={small,medium,full} (CPU default: small).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import (SIZES, build_index, dataset, emit,
                               fixed_batches, time_batches)
from repro.api.compile_cache import enable_persistent_cache
from repro.data.strings import make_workload

DATASET_NAMES = ("dblp", "usps", "sprot")
KINDS = ("tt", "et", "ht")


def _queries_by_len(ds, n, lens=(2, 6, 10, 14, 18, 22)):
    qs = make_workload(ds, n * 3, seed=7, min_len=2, max_len=max(lens) + 2)
    by = {}
    for L in lens:
        sel = [q[:L] for q in qs if len(q) >= L][:n]
        if sel:
            by[L] = sel
    return by


def b1_datasets():
    rows = []
    for name in DATASET_NAMES:
        ds = dataset(name)
        lens = [len(s) for s in ds.strings]
        # rules applicable per string (sampled)
        import random
        rnd = random.Random(0)
        sample = rnd.sample(ds.strings, min(500, len(ds.strings)))
        apps = [sum(1 for lhs, rhs in ds.rules if rhs in s) for s in sample]
        rows.append([ds.name, len(ds.strings),
                     f"{np.mean(lens):.0f}/{np.max(lens)}", len(ds.rules),
                     f"{np.mean(apps):.2f}/{np.max(apps)}"])
    emit(rows, ["dataset", "n_strings", "len avg/max", "n_rules",
                "rules_per_string avg/max"])
    return rows


def b2_space(include_bl: bool = True, compression: str = "packed"):
    """Bytes per string; BL = naive expand-all-rewritings baseline (expected
    to blow up -- capped and reported as a lower bound when it does).

    When ``compression`` is ``"packed"`` (the default) each kind also
    gets a format-v4 compressed column (``*_v4``) so the compressed
    footprint sits next to the paper's reported 160-200 B/string for
    the uncompressed C++ structures (Table 2).
    """
    packed = compression == "packed"
    rows = []
    for name in DATASET_NAMES:
        ds = dataset(name)
        row = [ds.name]
        if include_bl:
            row.append(_bl_bytes_per_string(ds))
        for kind in KINDS:
            idx = build_index(ds, kind, alpha=0.5)
            row.append(round(idx.stats.bytes_per_string, 1))
            if packed:
                pix = build_index(ds, kind, alpha=0.5,
                                  compression="packed")
                row.append(round(pix.stats.bytes_per_string, 1))
        # Fig 5 breakdown for the paper's SPROT plot equivalent
        idx = build_index(ds, "ht", alpha=0.5)
        row += [idx.stats.bytes_dict_nodes // max(idx.stats.n_strings, 1),
                idx.stats.bytes_syn_nodes // max(idx.stats.n_strings, 1),
                idx.stats.bytes_rule_side // max(idx.stats.n_strings, 1)]
        rows.append(row)
    kind_cols = [c for k in KINDS
                 for c in ([k.upper(), f"{k.upper()}_v4"] if packed
                           else [k.upper()])]
    emit(rows, ["dataset", "BL"] + kind_cols
         + ["ht_dict_B", "ht_syn_B", "ht_rule_B"])
    if packed:
        print("(paper Table 2 reports 160-200 B/string for the "
              "uncompressed structures; *_v4 columns are the packed "
              "format-v4 layout)\n")
    return rows


def _bl_bytes_per_string(ds, cap: int = 2_000_000):
    """Baseline: materialize every rewriting as a plain trie entry."""
    from repro.core import CompletionIndex, make_rules

    out = []
    scores = []
    inv = {}
    for lhs, rhs in ds.rules:
        inv.setdefault(rhs, []).append(lhs)
    blew_up = False
    for s, r in zip(ds.strings, ds.scores):
        variants = {s}
        for rhs, lhss in inv.items():
            if rhs in s and len(variants) < 64:
                for lhs in lhss:
                    variants |= {v.replace(rhs, lhs, 1) for v in list(variants)}
        out.extend(variants)
        scores.extend([int(r)] * len(variants))
        if len(out) > cap:
            blew_up = True
            break
    idx = CompletionIndex.build(out, scores, make_rules([]), kind="plain")
    per = idx.stats.bytes_total / max(len(ds.strings), 1)
    return f">{per:.0f}(failed)" if blew_up else round(per, 1)


def b3_construction():
    rows = []
    for name in DATASET_NAMES:
        ds = dataset(name)
        row = [ds.name]
        for kind in KINDS:
            t0 = time.perf_counter()
            build_index(ds, kind, alpha=0.5)
            row.append(round(time.perf_counter() - t0, 2))
        rows.append(row)
    emit(rows, ["dataset", "tt_s", "et_s", "ht_s"])
    return rows


def b4_lookup(k: int = 10, batch: int = 256):
    rows = []
    for name in DATASET_NAMES:
        ds = dataset(name)
        by_len = _queries_by_len(ds, SIZES["queries"] // 4)
        idxs = {kind: build_index(ds, kind, alpha=0.5) for kind in KINDS}
        for L, qs in by_len.items():
            row = [ds.name, L]
            for kind in KINDS:
                batches = fixed_batches(qs, batch)
                if not batches:
                    row.append("")
                    continue
                sec = time_batches(
                    lambda b, ix=idxs[kind]: ix.complete(b, k=k), batches)
                row.append(round(sec * 1e6, 1))
            rows.append(row)
    emit(rows, ["dataset", "query_len", "tt_us", "et_us", "ht_us"])
    return rows


def b5_alpha(k: int = 10, batch: int = 256, name: str = "sprot"):
    ds = dataset(name)
    qs = make_workload(ds, SIZES["queries"] // 2, seed=3, max_len=18)
    rows = []
    for alpha in (0.0, 0.25, 0.5, 0.75, 1.0):
        idx = build_index(ds, "ht", alpha=alpha)
        batches = fixed_batches(qs, batch)
        sec = time_batches(lambda b: idx.complete(b, k=k), batches)
        rows.append([alpha, round(idx.stats.bytes_per_string, 1),
                     idx.stats.n_rules_expanded,
                     round(sec * 1e6, 1)])
    emit(rows, ["alpha", "bytes_per_string", "rules_expanded", "us_per_q"])
    return rows


def b6_scalability(k: int = 10, batch: int = 256):
    from repro.data.strings import make_usps

    full = SIZES["usps"]
    fracs = (0.2, 0.4, 0.6, 0.8, 1.0)
    rows = []
    base = make_usps(n=full, seed=0)
    order = np.argsort(-base.scores)   # paper: top-N by decreasing score
    for f in fracs:
        n = max(int(full * f), 1000)
        sel = order[:n]
        strings = [base.strings[i] for i in sel]
        scores = base.scores[sel]
        from repro.core import CompletionIndex, make_rules
        row = [n]
        qs = None
        for kind in KINDS:
            idx = CompletionIndex.build(strings, scores,
                                        make_rules(base.rules), kind=kind,
                                        alpha=0.5)
            if qs is None:
                from repro.data.strings import StringDataset
                sub = StringDataset("USPS", strings, scores, base.rules)
                qs = make_workload(sub, SIZES["queries"] // 4, seed=5)
            batches = fixed_batches(qs, batch)
            sec = time_batches(lambda b, ix=idx: ix.complete(b, k=k), batches)
            row += [round(idx.stats.bytes_per_string, 1),
                    round(sec * 1e6, 1)]
        rows.append(row)
    emit(rows, ["n_strings", "tt_B", "tt_us", "et_B", "et_us",
                "ht_B", "ht_us"])
    return rows


ALL = {
    "b1": ("Table 1: dataset characteristics", b1_datasets),
    "b2": ("Table 2 + Fig 5: bytes per string", b2_space),
    "b3": ("Fig 6: construction time (s)", b3_construction),
    "b4": ("Fig 7: top-10 lookup vs query length (us)", b4_lookup),
    "b5": ("Fig 8: HT alpha sweep (us)", b5_alpha),
    "b6": ("Fig 9: scalability on USPS", b6_scalability),
}


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(ALL))
    ap.add_argument("--compression", default="packed",
                    choices=["none", "packed"],
                    help="layout for the b2 space table's extra columns: "
                         "packed adds a format-v4 bytes/string column "
                         "per kind next to the paper's 160-200 B target; "
                         "none reproduces the paper table verbatim")
    args = ap.parse_args()
    enable_persistent_cache()
    for key, (title, fn) in ALL.items():
        if args.only and key != args.only:
            continue
        print(f"-- {key}: {title} --")
        if key == "b2":
            fn(compression=args.compression)
        else:
            fn()


if __name__ == "__main__":
    main()
