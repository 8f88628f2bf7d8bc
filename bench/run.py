"""Run one cell of the benchmark on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process: it makes the cell's dictionary (from the configuration's
``data_seed``) and its traffic (the order and timing from ``--seed``),
builds the index through the program's ``build_index`` or loads it from
the index store (``bench/.store/``, written after the window of the run
that built it), warms up every program the traffic runs, and drives the
service for ``--seconds`` seconds.  After the window it checks every
answer it kept against the configuration's plain reference and prints,
as the last line of standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics read from a profiler trace of a slice of the window),
``device``, with ``--trace 1`` ``breakdown``, and last ``compared``: each
number the verdict compares, beside its limit.  The same numbers end
standard error.  Earlier lines say what ran: the device, the data, the
dispatches the programs traced, the compiles inside the window, and the
traffic's own counts (for typists, with every call into the service that
stalled and what the host did meanwhile).

It exits non-zero and prints no result off a TPU, with fewer chips than
the cell asks for, on a device kind ``bench/peaks.json`` does not know,
or in a checkout without the program (``src/repro``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: seconds of the window a ``--trace 1`` run traces (its first ones); the
#: engine's loops put some 700,000 operations a second on the trace
TRACE_S = 1.0


def say(line: str) -> None:
    print(line, flush=True)


def prepare_process(root: Path) -> None:
    """Before JAX is imported: the program and ``bench`` (as a package)
    on the path, and the TPU runtime's logs off (they go to a fixed path
    under /tmp otherwise).  The script's own directory leaves the path,
    so that bench/trace.py cannot shadow the standard library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is kept, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / "bench" / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def start(prog: str, workload: str, root: Path = ROOT):
    """What every entry point does first: find the program in the
    checkout, prepare the process, insist on a TPU with the chips the
    cell asks for and a device kind ``bench/peaks.json`` knows, and turn
    on the compile cache.  Returns ``(cell, peaks)``, or ``None`` after
    saying on standard error why not."""
    if not (root / "src" / "repro").is_dir():
        print(f"{prog}: the program is not in this checkout "
              f"({root / 'src' / 'repro'} is missing)", file=sys.stderr)
        return None
    prepare_process(root)
    import jax

    from bench import harness

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"{prog}: no TPU (JAX sees {devices[0].platform!r} devices); "
              f"the benchmark does not fall back to the CPU",
              file=sys.stderr)
        return None
    cell = harness.resolve(harness.load_benchmark(root), workload, root)
    if len(devices) < cell.chips:
        print(f"{prog}: {workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return None
    peak = harness.peaks(devices[0].device_kind, root)
    cache = enable_compile_cache(root)
    say(f"device: platform {devices[0].platform}, device_kind "
        f"{devices[0].device_kind}, count {len(devices)}; peaks {peak}; "
        f"compile cache {cache}")
    return cell, peak


def store_path(cfg: dict, store: Path, root: Path = ROOT) -> Path:
    """Where the index store keeps ``cfg``'s built index: one file per
    configuration, keyed by the configuration, its dataset module and
    every file of the program under ``src/repro``, so that a change to
    any of them builds afresh."""
    h = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    files = [root / "bench" / "datasets" / f"{cfg['dataset']}.py"]
    files += sorted(p for p in (root / "src" / "repro").rglob("*")
                    if p.is_file() and "__pycache__" not in p.parts)
    for f in files:
        h.update(str(f.relative_to(root)).encode())
        h.update(f.read_bytes())
    return store / f"{cfg['name']}-{h.hexdigest()[:20]}.npz"


def build(cfg: dict, log=say, store: Path | None = None):
    """The configuration's data (from its ``data_seed``) and its index on
    the device, loaded from the index store when ``store`` holds it;
    returns ``(dataset, index, device bytes in use after placement or
    None, path to save the index to after the window or None)``."""
    import jax

    from repro.api import CompletionIndex, IndexSpec, build_index
    from repro.core import make_rules
    from repro.core.engine.primitives import record_dispatch

    from bench import harness, loadgen

    t0 = time.perf_counter()
    ds = harness.dataset(cfg).make(
        int(cfg["n_strings"]), seed=loadgen.seed_seq(cfg["data_seed"], 0))
    t1 = time.perf_counter()
    path = store_path(cfg, store) if store is not None else None
    loaded = path is not None and path.is_file()
    with record_dispatch() as paths:
        if loaded:
            index = CompletionIndex.load(str(path))
        else:
            index = build_index(ds.strings, ds.scores, make_rules(ds.rules),
                                IndexSpec(**cfg["spec"]))
        jax.block_until_ready(index.device)
    t2 = time.perf_counter()
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    log(f"data: {cfg['name']}: {len(ds.strings)} strings generated, "
        f"{index.stats.n_strings} distinct indexed, {len(ds.rules)} rules "
        f"(the source declares {cfg.get('rules_published', 'n/a')}); "
        f"generation {t1 - t0:.3f} s, "
        f"{'store load' if loaded else 'host build'} {t2 - t1:.3f} s; kind "
        f"{index.kind}, substrate {index.substrate}, layout "
        f"{index.compression}; device bytes in use after placement "
        f"{in_use if in_use is not None else 'not reported'}; dispatches "
        f"traced: {' '.join(sorted(paths)) or '-'}")
    return ds, index, in_use, None if loaded else path


def save_to_store(index, path: Path | None, log=say) -> None:
    """Write a freshly built index to the store (atomically: a run cut
    short leaves no half-written file behind)."""
    if path is None:
        return
    t0 = time.perf_counter()
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    index.save(str(tmp))
    os.replace(tmp, path)
    log(f"store: saved {path.name}, {path.stat().st_size} bytes, in "
        f"{time.perf_counter() - t0:.3f} s")


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, store: Path | None = None,
             service_wrap=None, built=None, ref=None, log=say):
    """Run ``cell`` once; returns ``(result, state)``: the contract's
    result object and what a caller may check further (the driver with
    its answers, the data, the reference).

    ``store`` is the index store's directory (none: build every time).
    ``service_wrap`` wraps the service the driver talks to, ``built``
    is a ``build`` result to reuse and ``ref`` a reference built over
    its data: the control (``bench/control.py``) and the tests use them,
    to put the control or a fault under the timed path and to build
    once for many runs."""
    import jax

    from repro.core.engine.primitives import record_dispatch

    from bench import harness, loadgen
    from bench.trace import Tracer

    counter = harness.CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    dev = jax.devices()[0]

    ds, index, in_use, to_store = (build(cfg, log, store) if built is None
                                   else built)
    n_strings = int(index.stats.n_strings)

    driver = cell.driver(traffic, ds, seed, seconds,
                         work_seed=cfg["data_seed"])
    svc = driver.service(index)
    if service_wrap is not None:
        svc = service_wrap(svc)
    t3 = time.perf_counter()
    with record_dispatch() as warm_paths:
        driver.warm(svc)
    t4 = time.perf_counter()
    tr, bu = counter.between(t3, t4)
    log(f"warm-up: {t4 - t3:.3f} s, {tr} programs traced, {bu} built or "
        f"loaded; dispatches traced: {' '.join(sorted(warm_paths)) or '-'}")

    tracer = loadgen.NO_TRACE
    if trace:
        tracer = Tracer(str(root / "bench" / ".trace"), min(TRACE_S, seconds),
                        lambda: driver.counters(svc))
    # what set-up left behind is collected now and kept out of later
    # collections, so that no pass over it lands in the window
    gc.collect()
    gc.freeze()
    with record_dispatch() as window_paths:
        t_win = driver.run(svc, tracer)
    gc.unfreeze()
    if trace:
        tracer.stop()
    setup_s = t_win - t_start
    pre = counter.between(t4, t_win)
    inside = counter.between(t_win, driver.window_end)
    log(f"compiles: {pre[0]} traced / {pre[1]} built between warm-up and "
        f"window; inside the window {inside[0]} traced / {inside[1]} built "
        f"(should be 0 / 0); dispatches traced in the window: "
        f"{' '.join(sorted(window_paths)) or '-'}")
    for line in driver.report():
        log(line)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"device memory: peak {peak}, in use {stats.get('bytes_in_use')}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics: dict = {}
    breakdown = None
    if trace:
        red = tracer.reduce()
        h0, h1 = tracer.host
        ctx = dict(red)
        for key, v in tracer.after.items():
            ctx[key] = v - tracer.before.get(key, 0) if key != "block" else v
        done = driver.resolved_times()
        ctx["answers"] = int(((done >= h0) & (done <= h1)).sum())
        for entry, read in cell.per_layer:
            v = read(ctx)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        log(f"trace: {red['trace_bytes']} bytes, slice {red['window_s']} s, "
            f"busy {red['busy_s']} s, {red['launches']} launches, counters "
            f"{ {k: ctx.get(k) for k in tracer.after} }, "
            f"{ctx['answers']} answers; idle by host span "
            f"{red['idle_by_span']}")
    else:
        values = driver.metrics()
        values["setup_s"] = setup_s
        values["device_bytes_per_string"] = (
            in_use / n_strings if in_use is not None else None)
        for entry in cell.end_to_end:
            v = values.get(entry["name"])
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    log(f"setup_s {setup_s} (process start to the window's first timed "
        f"operation)")

    _, attempted, failed = driver.outcomes()
    # after the window, so that a cold run's set-up does not pay for it
    save_to_store(index, to_store, log)
    # the program's state goes before the reference is built
    del svc, index
    gc.collect()
    t5 = time.perf_counter()
    if ref is None:
        ref = harness.reference(cfg).build(ds.strings, ds.scores, ds.rules)
    k = int(traffic["k"])
    wrong, checked, examples = 0, 0, []
    memo: dict = {}
    for q, got in driver.answers():
        key = (q, tuple(got))
        v = memo.get(key, False)
        if v is False:
            v = memo[key] = ref.verdict(q, got, k)
        checked += 1
        if v is not None:
            wrong += 1
            if len(examples) < 3:
                examples.append(f"{q!r}: {v}")
    log(f"check: {checked} answers compared with the reference "
        f"({len(memo)} distinct) in {time.perf_counter() - t5:.3f} s; "
        f"{wrong} wrong" + (f", e.g. {'; '.join(examples)}" if examples
                            else ""))
    compared = {"wrong_answers": {"value": wrong, "limit": 0},
                "never_answered": {"value": failed, "limit": 0}}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in compared.values()) and checked > 0,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    state = types.SimpleNamespace(driver=driver, ds=ds, ref=ref, k=k)
    return result, state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    started = start("bench/run.py", args.workload)
    if started is None:
        return 2
    cell, peak = started
    result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, store=ROOT / "bench" / ".store")
    peak_bytes = result["device"]["memory_peak_bytes"]
    say(f"device memory peak {peak_bytes} B of {peak['hbm_bytes']} B "
        f"({100 * (peak_bytes or 0) / peak['hbm_bytes']:.2f}%)")
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
