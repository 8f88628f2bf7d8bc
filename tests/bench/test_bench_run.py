"""``bench/run.py`` times nothing off a TPU and prints no result: it exits
non-zero on the CPU, and in a checkout that holds only the benchmark.
And its index store builds a configuration once and loads it after."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.run import build, save_to_store, store_path  # noqa: E402

ARGS = ["--workload", "dblp-tt.oneshot", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            return False
    return True


def test_off_a_tpu_it_exits_non_zero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_a_checkout_of_the_benchmark_alone_exits_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_the_index_store_builds_once_and_loads_after(tmp_path):
    """The first build writes the store after the window; later builds of
    the same configuration and program load it and answer alike."""
    cfg = {"name": "tiny-store", "dataset": "usps", "n_strings": 300,
           "data_seed": 4, "spec": {"kind": "et", "substrate": "jnp"},
           "reference": "prefix_scan"}
    quiet = dict(log=lambda line: None, store=tmp_path)
    ds, first, _, to_store = build(cfg, **quiet)
    assert to_store == store_path(cfg, tmp_path) and not to_store.exists()
    save_to_store(first, to_store, log=lambda line: None)
    assert to_store.is_file() and not list(tmp_path.glob("*.tmp.npz"))
    ds2, again, _, none = build(cfg, **quiet)
    assert none is None and ds2.strings == ds.strings
    queries = ["jim", "ma", "rob", "x"]
    assert again.complete(queries, k=5) == first.complete(queries, k=5)
    assert again.substrate == first.substrate
    # another configuration has another file
    other = dict(cfg, n_strings=301)
    assert store_path(other, tmp_path) != to_store
