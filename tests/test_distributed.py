"""Distributed behaviour on simulated multi-device meshes (subprocesses set
XLA_FLAGS before jax init; the main pytest process stays single-device)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str, n_devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_completion_matches_oracle():
    code = """
import jax, json
from repro.core import make_rules
from repro.core.distributed import ShardedCompletionIndex
from repro.core.oracle import OracleIndex

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
strings = [f"record {i:03d} entry" for i in range(64)] + [
    "andrew pavlo", "william smith"]
scores = list(range(1, len(strings) + 1))
rules = make_rules([("andy", "andrew"), ("bill", "william"), ("rec", "record")])
oracle = OracleIndex(strings, scores, rules)
idx = ShardedCompletionIndex(strings, scores, rules, mesh=mesh, kind="ht",
                             alpha=0.5)
qs = ["andy", "bill s", "rec 00", "record 01", "zzz", "entry", "r", "re"]
got = idx.complete(qs, k=5)
for q, row in zip(qs, got):
    exp = [s for s, _ in oracle.complete(q, 5)]
    assert [s for s, _ in row] == exp, (q, row, exp)
# the mesh program is compiled once per batch shape, not once per call
misses = idx._compile_cache.misses
assert idx.complete(qs, k=5) == got
assert idx._compile_cache.misses == misses
print("OK")
"""
    assert "OK" in run_subprocess(code)


def test_lm_sharded_train_step_matches_single_device():
    """The sharded train step must be numerically equivalent (small tol) to
    single-device execution: same loss for same batch."""
    code = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import all_archs
from repro.configs.cells import make_train_step
from repro.distributed import sharding as sh
from repro.models import transformer as tf
from repro.optim import init_optimizer

spec = all_archs()["granite-moe-1b-a400m"]
cfg = dataclasses.replace(spec.make_smoke_config(), moe_experts=4)
params, _ = tf.init_lm(jax.random.PRNGKey(0), cfg)
opt = init_optimizer(spec.optimizer, params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32))),
         "targets": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32))),
         "mask": jnp.ones((8, 32), bool)}
step = make_train_step(tf.loss_fn, cfg, spec.optimizer)

# single device
_, _, m1 = jax.jit(step)(params, opt, batch)
loss1 = float(m1["loss"])

# sharded over (2 data x 2 model)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg2 = dataclasses.replace(cfg, tp_heads=2)
params2, _ = tf.init_lm(jax.random.PRNGKey(0), cfg2)
opt2 = init_optimizer(spec.optimizer, params2)
step2 = make_train_step(tf.loss_fn, cfg2, spec.optimizer)
with sh.use_mesh(mesh):
    _, _, m2 = jax.jit(step2)(params2, opt2, batch)
loss2 = float(m2["loss"])
# tp=2 padded-head layout is mathematically identical GQA; same init seed
assert abs(loss1 - loss2) < 5e-2, (loss1, loss2)
print("OK", loss1, loss2)
"""
    out = run_subprocess(code, n_devices=4)
    assert "OK" in out


def test_flash_decode_sharded_matches_dense():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import sharding as sh
from repro.models import layers as L

rng = np.random.default_rng(0)
B, H, KV, Sc, hd = 4, 4, 2, 32, 16
q = jnp.asarray(rng.normal(size=(B, H, 1, hd)).astype(np.float32))
k = jnp.asarray(rng.normal(size=(B, KV, 1, hd)).astype(np.float32))
v = jnp.asarray(rng.normal(size=(B, KV, 1, hd)).astype(np.float32))
ck = jnp.asarray(rng.normal(size=(B, KV, Sc, hd)).astype(np.float32))
cv = jnp.asarray(rng.normal(size=(B, KV, Sc, hd)).astype(np.float32))
pos = jnp.int32(17)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with sh.use_mesh(mesh):
    out_s, (ck_s, cv_s) = jax.jit(
        lambda *a: L._flash_decode_sharded(*a, None, mesh))(q, k, v, ck, cv, pos)

# dense reference (single device semantics)
g = H // KV
ck2 = ck.at[:, :, 17, :].set(k[:, :, 0, :])
cv2 = cv.at[:, :, 17, :].set(v[:, :, 0, :])
kk = jnp.repeat(ck2, g, axis=1)
vv = jnp.repeat(cv2, g, axis=1)
s = jnp.einsum("bnqh,bnkh->bnqk", q, kk) / np.sqrt(hd)
valid = jnp.arange(Sc)[None, :] <= 17
s = jnp.where(valid[:, None, None, :], s, -1e30)
w = jax.nn.softmax(s, axis=-1)
ref = jnp.einsum("bnqk,bnkh->bnqh", w, vv)
err = float(jnp.abs(out_s - ref).max())
assert err < 1e-5, err
assert float(jnp.abs(ck_s - ck2).max()) == 0.0
print("OK", err)
"""
    assert "OK" in run_subprocess(code)


def test_compressed_allreduce_error_feedback():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import sharding as sh
from repro.distributed.compression import (compress_grads, init_error_state)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
g = {"w": jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))}
with sh.use_mesh(mesh):
    err = init_error_state(g, "data")
    out, err = compress_grads(g, err, "data")
    # replicated input => mean == input, up to int8 quantization error
    diff = float(jnp.abs(out["w"] - g["w"]).max())
    scale = float(jnp.abs(g["w"]).max()) / 127
    assert diff <= scale + 1e-6, (diff, scale)
    # error feedback: compressing the same grad repeatedly converges so the
    # *accumulated* mean approaches the true value
    acc = jnp.zeros_like(g["w"])
    e = init_error_state(g, "data")
    for _ in range(8):
        o, e = compress_grads(g, e, "data")
        acc = acc + o["w"]
    mean_err = float(jnp.abs(acc / 8 - g["w"]).max())
    assert mean_err < scale / 2, (mean_err, scale)
print("OK")
"""
    assert "OK" in run_subprocess(code)


@pytest.mark.slow
def test_dryrun_smoke_production_mesh():
    """One cell per family on the real (16,16) mesh with smoke configs."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.configs import all_archs
from repro.launch.dryrun import run_cell
from repro.launch.mesh import make_production_mesh

mesh = make_production_mesh()
archs = all_archs()
for aid, shape in [("granite-moe-1b-a400m", "train_4k"),
                   ("gin-tu", "molecule"),
                   ("dlrm-rm2", "serve_p99"),
                   ("autocomplete-dblp", "serve_1k")]:
    r = run_cell(archs[aid], shape, mesh, smoke=True)
    assert r["status"] == "OK", (aid, shape, r.get("error"))
print("OK")
"""
    assert "OK" in run_subprocess(code, n_devices=1, timeout=1800)
