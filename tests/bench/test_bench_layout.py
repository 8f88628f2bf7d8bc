"""BENCHMARK.json and the files it names: every cell resolves to its
configuration, traffic and readers, the file keeps to the benchmark's
contract, and a new configuration, mix and metric need only new files and
entries."""

import json
import re
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_benchmark(ROOT)


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench", "tests/bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


def test_names_units_and_entry_keys():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_resolves_to_its_files(cell):
    c = harness.resolve(BENCH, cell, ROOT)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    assert callable(harness.dataset(c.config).make)
    assert callable(harness.reference(c.config).build)
    assert isinstance(c.driver, type) and hasattr(c.driver, "run")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(callable(read) for _, read in c.per_layer)
    # every per-layer metric of the cell moves an end-to-end metric the
    # cell reports
    assert {m["moves"] for m, _ in c.per_layer} <= e2e


def test_configs_list_what_they_cut():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert c["file"].startswith("bench/configs/")


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite", ROOT)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu", ROOT)


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A throwaway configuration, traffic mix, driver and per-layer
    metric, added as files and entries in a copy of the benchmark,
    resolve and run."""
    for d in ("configs", "traffic", "metrics", "drivers"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    bench = json.loads(json.dumps(BENCH))
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps({
        "name": "tiny", "dataset": "usps", "n_strings": 400,
        "data_seed": 1, "spec": {"kind": "et", "substrate": "jnp"},
        "reference": "prefix_scan", "reduced": ["n_strings"]}))
    (tmp_path / "bench/drivers/tiny_batches.py").write_text(
        "from bench.drivers.oneshot import Driver as OneShot\n\n\n"
        "class Driver(OneShot):\n    seen_here = True\n")
    (tmp_path / "bench/traffic/tiny-batches.json").write_text(json.dumps({
        "driver": "tiny_batches", "k": 5, "batch": 16, "pool_batches": 2,
        "keep_every": 1,
        "queries": {"generator": "uniform", "min_len": 1, "max_len": 6}}))
    (tmp_path / "bench/metrics/batches_seen.py").write_text(
        "def read(ctx):\n    return ctx.get('batches')\n")
    bench["configs"].append({"name": "tiny", "source": "test", "why": "t",
                             "file": "bench/configs/tiny.json",
                             "reduced": ["n_strings"]})
    bench["workloads"].append({"name": "tiny.batches", "config": "tiny",
                               "traffic": "tiny-batches", "chips": 1,
                               "why": "t"})
    for m in bench["end_to_end"]:
        if m["name"] == "answers_per_s":
            m["workloads"].append("tiny.batches")
    bench["per_layer"].append({"name": "batches_seen", "unit": "batches",
                               "better": "higher", "source": "device_trace",
                               "layer": "index", "moves": "answers_per_s",
                               "workloads": ["tiny.batches"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(harness.load_benchmark(tmp_path), "tiny.batches",
                           tmp_path)
    assert [m["name"] for m, _ in cell.per_layer] == ["batches_seen"]
    assert cell.driver.seen_here
    assert cell.per_layer[0][1]({"batches": 3}) == 3
    from bench.run import run_cell
    result, state = run_cell(cell, 2**31 + 7, 0.3, False,
                             t_start=time.perf_counter(), root=tmp_path,
                             log=lambda line: None)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    # device memory is not reported off a chip, so only the host-clock
    # metrics are there
    assert set(result["metrics"]) == {"answers_per_s", "setup_s"}
    assert list(result)[-1] == "compared"
