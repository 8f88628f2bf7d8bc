"""Benchmark harness: one entry per paper table/figure + roofline + beyond.

  PYTHONPATH=src python -m benchmarks.run             # all
  PYTHONPATH=src python -m benchmarks.run --only b4
  REPRO_BENCH_SCALE=full ... python -m benchmarks.run # paper-scale (1M)
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()

    from benchmarks import beyond_paper, paper_tables, roofline, substrates
    from benchmarks.common import SCALE
    from repro.api.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    suites = dict(paper_tables.ALL)
    suites.update(beyond_paper.ALL)
    suites.update(substrates.ALL)

    print(f"== repro benchmarks (scale={SCALE}) ==\n")
    for key, (title, fn) in suites.items():
        if args.only and key != args.only:
            continue
        print(f"-- {key}: {title} --")
        t0 = time.perf_counter()
        fn()
        print(f"({key} took {time.perf_counter() - t0:.1f}s)\n")

    if not args.only and not args.skip_roofline:
        print("-- roofline (from dry-run artifacts) --")
        roofline.main()


if __name__ == "__main__":
    main()
