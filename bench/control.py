"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <n>,<n>,... [--program 0|1]

The configurations state no precision; the guarantee they state is the
exact top-k under every synonym rule.  The control breaks it the way the
cheapest shortcut would: it is the plain reference with the rules left
out (what the program's own ``kind="plain"`` index answers), put in the
program's place under the timed path (``ControlService`` wraps the
service the driver talks to, and every answer the driver keeps is the
control's).  The harness's own verdict must then read ``correct`` false.

One process builds (or loads) the cell's index once and, for each seed,
runs the cell as ``run.py`` does with the control in the program's
place, and first, with ``--program 1``, the program itself: one JSON
line per run with the numbers compared.  The benchmark's own runs never
run it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class _Ticket:
    """A keystroke's ticket whose answer is the control's for the
    ticket's prefix, once the program's own has resolved."""

    def __init__(self, real, answer):
        self.real, self._answer = real, answer

    def __getattr__(self, name):
        return getattr(self.real, name)

    @property
    def done(self):
        return self.real.done

    @property
    def results(self):
        return self._answer(self.real.prefix.decode())


class _Session:
    def __init__(self, real, answer):
        self.real, self._answer = real, answer

    def submit(self, ch):
        return _Ticket(self.real.submit(ch), self._answer)

    def close(self):
        self.real.close()


class ControlService:
    """The service with every answer replaced by ``scan``'s (a plain
    reference without rules), at the same prefixes and ``k``."""

    def __init__(self, real, scan, k: int):
        self.real, self.scan, self.k = real, scan, k
        self._memo: dict = {}

    def __getattr__(self, name):
        return getattr(self.real, name)

    def answer(self, q: str):
        a = self._memo.get(q)
        if a is None:
            a = self._memo[q] = self.scan.complete(q, self.k)
        return a

    def open_session(self, k):
        return _Session(self.real.open_session(k=k), self.answer)

    def complete(self, queries, k=10):
        self.real.complete(queries, k=k)
        return [self.answer(q) for q in queries]


def control_wrap(ds, k: int, reference):
    """The ``service_wrap`` that puts the control in the program's
    place: ``reference`` (the configuration's reference module) built
    over the same strings and scores with no rules."""
    scan = reference.build(ds.strings, ds.scores, [])
    return lambda svc: ControlService(svc, scan, k)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0,
                    help="also run the program itself on each seed")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench import run
    started = run.start("bench/control.py", args.workload, ROOT)
    if started is None:
        return 2
    cell, _ = started
    from bench import harness

    ds, index, in_use, to_store = run.build(cell.config,
                                            store=ROOT / "bench" / ".store")
    run.save_to_store(index, to_store)
    built = (ds, index, in_use, None)
    ref = harness.reference(cell.config).build(ds.strings, ds.scores,
                                               ds.rules)
    wrap = control_wrap(ds, int(cell.traffic["k"]),
                        harness.reference(cell.config))
    sides = [("program", None)] * args.program + [("control", wrap)]
    for seed in [int(s) for s in args.seeds.split(",")]:
        for side, w in sides:
            t0 = time.perf_counter()
            result, _ = run.run_cell(cell, seed, args.seconds, False,
                                     t_start=t0, built=built, ref=ref,
                                     service_wrap=w)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "side": side,
                "correct": result["correct"],
                "attempted": result["attempted"],
                "failed": result["failed"],
                "compared": {k: v["value"]
                             for k, v in result["compared"].items()},
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()}}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
