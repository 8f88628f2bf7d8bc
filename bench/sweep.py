"""Find the knee of an open-loop typists cell by a sweep of arrival rates.

    python3 bench/sweep.py --workload usps-et.typists --seed <n> \\
        --seconds 8 --rates 25,50,100,200

One process builds the cell's index once (or loads it from the index
store), then for each rate makes the
cell's traffic at that rate, with ``block`` set by the cell's rule (the
smallest power of two at least twice the mean number of typists in
flight), warms up and drives a window.  One line per rate: the keystroke
latency from due time, the generator's backlog over the window and the
scheduler's counts.  The knee is the highest rate at which the backlog
does not grow over the window and the p99 stays under ``LIMIT_MS``; the
last line also gives the highest rate that keeps up (no growing
backlog, whatever the latency), and the sweep stops after two rates in
a row that do not keep up.  The benchmark's own runs never sweep: a
cell's rate and block are fixed in its traffic file.  Like ``run.py``,
it needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: the p99 limit: Nielsen's limit of a response that feels instantaneous
LIMIT_MS = 100.0
#: how long a rate's window may run past its close (an overloaded rate
#: would otherwise drain its backlog for the driver's full minute)
GRACE_S = 10.0


def block_for(driver) -> int:
    """Smallest power of two at least twice the mean number of typists
    in flight (each holds a lane from its first key to its last)."""
    import numpy as np
    first = driver.due[driver.offset[:-1]]
    last = driver.due[driver.offset[1:] - 1]
    horizon = driver.preroll + driver.seconds
    in_flight = float(np.sum(last - first)) / horizon
    b = 1
    while b < 2 * in_flight:
        b *= 2
    return b


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="typist arrival rates per second, comma-separated")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench import run
    started = run.start("bench/sweep.py", args.workload, ROOT)
    if started is None:
        return 2
    cell, _ = started
    ds, index, _, to_store = run.build(cell.config,
                                       store=ROOT / "bench" / ".store")
    run.save_to_store(index, to_store)
    driver_cls = cell.driver
    knee, sustained, misses = None, None, 0
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dict(cell.traffic, typists_per_s=rate)
        probe = driver_cls(traffic, ds, args.seed, args.seconds,
                           work_seed=cell.config["data_seed"])
        traffic["block"] = block_for(probe)
        driver = driver_cls(traffic, ds, args.seed, args.seconds,
                            work_seed=cell.config["data_seed"])
        driver.grace_s = GRACE_S
        svc = driver.service(index)
        t0 = time.perf_counter()
        driver.warm(svc)
        warm_s = time.perf_counter() - t0
        driver.run(svc)
        m = driver.metrics()
        lat, attempted, failed = driver.outcomes()
        st = svc.scheduler.stats
        backlog = driver.backlog
        # growing: more than 100 ms of keys behind at the end, and more
        # than twice what was behind at the window's start
        # (a loop that stalled for seconds took fewer samples: it is
        # behind too)
        grows = (len(backlog) < args.seconds - 1 or backlog[-1] > max(
            2 * backlog[0], 0.1 * attempted / args.seconds))
        kept_up = not grows and failed == 0
        ok = kept_up and m["keystroke_p99_ms"] < LIMIT_MS
        if ok:
            knee = rate
        if kept_up:
            sustained, misses = rate, 0
        else:
            misses += 1
        print(json.dumps({
            "rate": rate, "block": traffic["block"], "warm_s": warm_s,
            "attempted": attempted, "failed": failed,
            "keystrokes_per_s": attempted / args.seconds,
            "p50_ms": m["keystroke_p50_ms"], "p99_ms": m["keystroke_p99_ms"],
            "backlog": backlog, "flushes": st.n_flushes,
            "mean_occupancy": st.mean_occupancy, "fallbacks": st.fallbacks,
            "kept_up": kept_up, "within_limit": ok}), flush=True)
        for line in driver.report():
            print("  " + line, flush=True)
        del svc, driver
        if misses == 2:
            break
    print(json.dumps({"knee_typists_per_s": knee,
                      "sustained_typists_per_s": sustained}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
