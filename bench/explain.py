"""Where a cell's time goes inside the program: its span recorder and the
counters of its scheduler and index, read over a run of the cell's
traffic.

    python3 bench/explain.py --workload <cell> --seed <n> --seconds <s> \
        [--trace-seconds <t>]

One process, set up as ``bench/run.py`` sets up a run (the same data,
index store, service, warm-up and driver), but with the program's span
recorder (``repro.spans.RECORDER``) on from the window's start, and the
driver's own spans (``open``, ``submit``, ``poll``, ``complete``) kept
in it too.  With ``--trace-seconds`` the profiler runs over the
window's first seconds and the report covers that slice; stopping the
profiler stalls the run, so the rest of the window is left out.  The
report, a line each:

- the counters' differences over the interval: flushes by kind, queue
  wait and answer lag per keystroke, host time in the dispatch calls and
  blocked in ``device_get`` per flush, fallbacks, and the index's
  lookups and exactness-retry rounds per 1,000 queries;
- for a driver that keeps its tickets (typists), each keystroke's
  latency from its due time split into the driver's delay (due to
  submit), queue wait and answer lag, and the answer lag split by the
  settle spans into the wait for the settle to begin, ``device_get`` and
  the resolve;
- time by span name: count, total and self time (total less the time of
  the spans directly inside);
- each call into the service over 50 ms with the program's time inside
  it by span, which says whether a stall waited in a dispatch, in
  ``device_get`` or on the host;
- with the profiler: device time by engine phase (what no phase claims
  is ``unnamed``) and the idle gaps named by the innermost span
  (``bench/program_trace.py``);
- what tracing costs on this host: a site with the recorder off, and a
  span begun and ended with it on.

The last line is the same as one JSON object.  This is a diagnosis, not
the benchmark: its latencies are those of a run with the recorder on,
and it checks no answer (``bench/run.py`` does both).
"""

from __future__ import annotations

import argparse
import bisect
import collections
import gc
import json
import shutil
import sys
import time
import timeit
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

#: a call into the service longer than this is listed with what it did
STALL_S = 0.05


class Window:
    """Stands in for the benchmark's tracer: hands the driver the
    program's recorder, turns it on at the window's start, and with
    ``trace_s`` runs the profiler over the window's first ``trace_s``
    seconds, reading the host clock right at the anchor's enter and
    exit."""

    def __init__(self, log_dir: Path, trace_s: float, counters):
        from repro.spans import RECORDER
        self.spans = RECORDER
        self.log_dir = log_dir
        self.trace_s = trace_s
        self.counters = counters
        self.start_at = None
        self.state = "idle"
        self.host = None          # the interval the report covers
        self.anchor_host = None   # host readings at the anchor's ends
        self.before = self.after = None

    def arm(self, start_at: float) -> None:
        self.start_at = start_at

    def tick(self, now: float) -> None:
        if self.start_at is None or now < self.start_at:
            return
        if self.state == "idle":
            at = now
            if self.trace_s > 0:
                import jax
                shutil.rmtree(self.log_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(str(self.log_dir),
                                         profiler_options=opts)
                from bench.trace import ANCHOR
                self._anchor = jax.profiler.TraceAnnotation(ANCHOR)
                self._anchor.__enter__()
                at = time.perf_counter()
                self.anchor_host = (at, at)
            self.spans.clear()
            self.spans.on = True
            self.before = self.counters()
            self.host = (at, None)
            self.state = "on"
        elif self.state == "on" and self.trace_s > 0 \
                and now >= self.host[0] + self.trace_s:
            self.stop()

    def stop(self) -> None:
        if self.state != "on":
            return
        self.spans.on = False
        self.after = self.counters()
        h1 = time.perf_counter()
        self.host = (self.host[0], h1)
        if self.trace_s > 0:
            import jax
            self._anchor.__exit__(None, None, None)
            self.anchor_host = (self.anchor_host[0], h1)
            jax.profiler.stop_trace()
        self.state = "done"


def program_counters(svc) -> dict:
    """The scheduler's and the index's counters, flattened."""
    import dataclasses
    out = {f"index.{k}": v for k, v in
           dataclasses.asdict(svc.index.lookups).items()}
    if svc.scheduler is not None:
        out.update({f"sched.{k}": v for k, v in
                    dataclasses.asdict(svc.scheduler.stats).items()})
    return out


def counters_line(d: dict, kinds: collections.Counter) -> str:
    """The counters' differences as means."""
    parts = []
    n_fl = d.get("sched.n_flushes", 0)
    if n_fl:
        lanes = max(d["sched.sum_occupancy"], 1)
        lag = d["sched.sum_answer_lag_s"] / max(d["sched.n_answered"], 1)
        parts.append(
            f"flushes {n_fl} ({dict(kinds)}), lanes consumed "
            f"{d['sched.sum_occupancy']}; queue wait "
            f"{1e3 * d['sched.sum_queue_wait_s'] / lanes:.4f} ms a "
            f"keystroke; answer lag {1e3 * lag:.4f} ms over "
            f"{d['sched.n_answered']} answered; dispatch "
            f"{1e3 * d['sched.dispatch_s'] / n_fl:.4f} ms and device_get "
            f"{1e3 * d['sched.settle_wait_s'] / n_fl:.4f} ms a flush; "
            f"fallbacks {d['sched.fallbacks']}")
    q = d.get("index.queries", 0)
    parts.append(
        f"index: {d.get('index.calls', 0)} calls, {q} queries, "
        f"{d.get('index.retry_rounds', 0)} retry rounds carrying "
        f"{d.get('index.retried_rows', 0)} rows "
        f"({1000.0 * d.get('index.retry_rounds', 0) / max(q, 1):.4f} per "
        f"1k queries)")
    return "counters: " + "; ".join(parts)


def by_span(items, lo: float, hi: float) -> dict:
    """{name: [count, total s, self s]} of the spans that started in
    [lo, hi].  Self time leaves out the spans directly inside by time,
    so a caller's span recorded after its call (``add``) counts its
    program spans as children too."""
    spans = sorted((s, -e, name) for name, s, e in items
                   if e is not None and lo <= s <= hi)
    out: dict[str, list] = {}
    stack: list[list] = []       # [end, name, child seconds]

    def close(top):
        row = out.setdefault(top[1], [0, 0.0, 0.0])
        row[2] -= top[2]

    for s, neg_e, name in spans:
        e = -neg_e
        while stack and stack[-1][0] < e:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += e - s
        row[2] += e - s
        stack.append([e, name, 0.0])
    while stack:
        close(stack.pop())
    return dict(sorted(out.items(), key=lambda x: -x[1][1]))


def stalls(items, lo: float, hi: float) -> list[dict]:
    """Calls into the service (the driver's spans: names without a dot)
    over ``STALL_S`` in [lo, hi], with the program's span time inside
    each by name."""
    prog = sorted((s, e, n) for n, s, e in items
                  if "." in n and e is not None)
    starts = [s for s, _, _ in prog]
    out = []
    for name, s, e in items:
        if "." in name or e is None or e - s <= STALL_S \
                or not lo <= s <= hi:
            continue
        inside: dict[str, float] = collections.defaultdict(float)
        j = bisect.bisect_left(starts, s)
        while j < len(prog) and prog[j][0] <= e:
            ps, pe, pn = prog[j]
            if pe <= e:
                inside[pn] += pe - ps
            j += 1
        out.append({"call": name, "at_s": s - lo, "ms": (e - s) * 1e3,
                    "inside_ms": {k: round(v * 1e3, 3)
                                  for k, v in sorted(inside.items())}})
    return sorted(out, key=lambda x: -x["ms"])


def keystroke_split(driver, items, links, lo: float, hi: float):
    """Means over the keystrokes submitted in [lo, hi] of the driver's
    delay (due to submit), queue wait and answer lag, and the lag's
    parts from the settle spans (ms); None for a driver without
    tickets."""
    tickets = getattr(driver, "tickets", None)
    if tickets is None:
        return None
    settle_at = {}        # flush -> [settle start, device_get end]
    flushes = []          # (span start, flush number)
    for (name, s, e), (_, rid, _) in zip(items, links):
        if name == "sched.settle" and rid not in settle_at:
            settle_at[rid] = [s, None]
        elif name == "sched.device_get" and rid in settle_at:
            settle_at[rid][1] = e
        elif name == "sched.flush":
            flushes.append((s, rid))
    flushes.sort()
    starts = [s for s, _ in flushes]
    rows = []
    for g, t in enumerate(tickets):
        if t is None or not t.done or t.consumed is None \
                or not lo <= t.created <= hi:
            continue
        due = driver.window_start + driver.due[g]
        resolved = t.created + t.latency_s
        row = [t.created - due, t.consumed - t.created,
               resolved - t.consumed, None, None, None]
        # the flush that took the ticket began its span just before it
        # read its start (the ticket's ``consumed``)
        j = bisect.bisect_right(starts, t.consumed) - 1
        sa = settle_at.get(flushes[j][1]) if j >= 0 else None
        if sa is not None and sa[1] is not None:
            row[3:] = [sa[0] - t.consumed, sa[1] - sa[0], resolved - sa[1]]
        rows.append(row)
    names = ["driver delay", "queue wait", "answer lag",
             "lag: flush to settle", "lag: settle to device_get end",
             "lag: resolve"]
    out = {"keystrokes": len(rows)}
    for i, n in enumerate(names):
        vals = [r[i] for r in rows if r[i] is not None]
        out[n] = 1e3 * sum(vals) / len(vals) if vals else None
    return out


def costs(n: int = 200_000) -> dict:
    """Seconds of one site with the recorder off, and of one span begun
    and ended with it on, on this host."""
    from repro.spans import Spans
    rec = Spans()
    site = "i = rec.begin('x', 1) if rec.on else -1\nif i >= 0: rec.end(i)"
    off = min(timeit.repeat(site, number=n, repeat=5,
                            globals={"rec": rec})) / n
    rec.on = True
    on = min(timeit.repeat(site, number=n // 10, repeat=5,
                           globals={"rec": rec})) / (n // 10)
    return {"off_site_s": off, "on_span_s": on}


def explain(cell, seed: int, seconds: float, trace_s: float = 0.0, *,
            root: Path = ROOT, store: Path | None = None,
            log=print) -> dict:
    """Run ``cell`` once with the recorder on and report (see the
    module's docstring); returns the report as a dict."""
    from bench import program_trace, run, trace
    cfg = cell.config
    ds, index, _, to_store = run.build(cfg, log, store)
    driver = cell.driver(cell.traffic, ds, seed, seconds,
                         work_seed=cfg["data_seed"])
    svc = driver.service(index)
    driver.warm(svc)
    win = Window(root / "bench" / ".trace", trace_s,
                 lambda: program_counters(svc))
    # as bench/run.py does: set-up's garbage is kept out of the window's
    # collections
    gc.collect()
    gc.freeze()
    driver.run(svc, win)
    gc.unfreeze()
    win.stop()
    items, links = list(win.spans.items), list(win.spans.links)
    win.spans.clear()
    lo, hi = win.host
    log(f"explain: {cell.name} seed {seed}, interval {hi - lo:.6f} s "
        f"({'the traced slice' if trace_s > 0 else 'the window'}), "
        f"{len(items)} spans kept")
    for line in driver.report():
        log(line)
    diff = {k: v - win.before.get(k, 0) for k, v in win.after.items()}
    kinds = collections.Counter(
        info[0] for (n, s, _), (_, _, info) in zip(items, links)
        if n == "sched.flush" and info is not None and lo <= s <= hi)
    log(counters_line(diff, kinds))
    split = keystroke_split(driver, items, links, lo, hi)
    if split is not None:
        log("keystroke split (ms, means): " + json.dumps(split))
    spans = by_span(items, lo, hi)
    log("spans (name: count, total s, self s): " + json.dumps(spans))
    slow = stalls(items, lo, hi)
    log(f"calls into the service over {STALL_S * 1e3:.0f} ms, {len(slow)}: "
        + json.dumps(slow[:8]))
    done = driver.resolved_times()
    out = {"cell": cell.name, "seed": seed, "interval_s": hi - lo,
           "metrics": driver.metrics(), "counters": diff,
           "answers": int(((done >= lo) & (done <= hi)).sum()),
           "flush_kinds": dict(kinds), "keystroke_split": split,
           "spans": spans, "stalls": slow[:8],
           "spans_per_s": sum(lo <= s <= hi for _, s, _ in items)
           / (hi - lo)}
    if trace_s > 0:
        import jax
        files = sorted(win.log_dir.rglob("*.xplane.pb"),
                       key=lambda f: f.stat().st_mtime)
        pd = jax.profiler.ProfileData.from_file(str(files[-1]))
        planes = trace.read_planes(pd)
        phases_of = program_trace.op_phases(
            text for _, text in index.compiled_programs())
        phases = program_trace.phase_busy(
            program_trace.read_phases(pd, phases_of))
        host = program_trace.to_trace_clock(
            [(n, s, e) for n, s, e in items if e is not None],
            win.anchor_host, planes["anchor"])
        red = trace.reduce_profile(planes)
        named = program_trace.name_gaps(program_trace.idle_gaps(planes),
                                        host)
        n_fl = diff.get("sched.n_flushes", 0)
        if n_fl:
            unit, per = "flush (ms)", {p: 1e3 * v / n_fl
                                       for p, v in phases.items()}
        else:
            unit, per = "answer (us)", {p: 1e6 * v / max(out["answers"], 1)
                                        for p, v in phases.items()}
        log(f"trace: slice {red['window_s']} s, busy {red['busy_s']} s, "
            f"phases (s) {phases}, per {unit} {per}; {n_fl} flushes, "
            f"{out['answers']} answers; idle by "
            f"innermost span {named['idle_by_span']}; longest gaps (name, "
            f"s, ms into the span, ms before its end) {named['idle_gaps']}")
        # what the device did during each slow call: a long program, or
        # a short one whose result came back late
        for st, (_, s0, s1) in zip(slow, program_trace.to_trace_clock(
                [(x["call"], lo + x["at_s"], lo + x["at_s"] + x["ms"] * 1e-3)
                 for x in slow], win.anchor_host, planes["anchor"])):
            st["device"] = program_trace.device_during(planes, s0, s1)
        if slow:
            log("slow calls on the device: " + json.dumps(slow[:8]))
        out.update(busy_s=red["busy_s"], window_s=red["window_s"],
                   phases_s=phases, phases_per=per, **named)
    out["costs"] = costs()
    log(f"costs: {out['costs']}, spans per second recorded "
        f"{out['spans_per_s']:.1f}")
    run.save_to_store(index, to_store, log)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    from bench import run
    started = run.start("bench/explain.py", args.workload)
    if started is None:
        return 2
    out = explain(started[0], args.seed, args.seconds, args.trace_seconds,
                  store=ROOT / "bench" / ".store", log=run.say)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
