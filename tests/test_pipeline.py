"""Pipeline parallelism (GPipe over the `pod` axis): exactness vs the
non-pipelined loss, and gradient flow through every stage."""

from tests.test_distributed import run_subprocess


def test_pp_loss_matches_plain_and_grads_flow():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import sharding as sh
from repro.distributed.pipeline import (make_pp_loss_fn, stack_stages,
                                        pipeline_bubble_fraction)
from repro.models import transformer as tf

cfg = tf.TransformerConfig(n_layers=4, d_model=64, n_heads=4, n_kv=2,
                           d_head=16, d_ff=128, vocab=97, loss_chunk=16)
params, _ = tf.init_lm(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
B, S = 8, 32
batch = {"tokens": jnp.asarray(rng.integers(0, 97, (B, S))),
         "targets": jnp.asarray(rng.integers(0, 97, (B, S))),
         "mask": jnp.ones((B, S), bool)}
loss_ref, _ = tf.loss_fn(params, batch, cfg)

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
pp_params = stack_stages(params, 2)
pp_loss = make_pp_loss_fn(cfg, n_micro=4)
with sh.use_mesh(mesh):
    loss_pp, _ = jax.jit(lambda p, b: pp_loss(p, b))(pp_params, batch)
    g = jax.jit(jax.grad(lambda p, b: pp_loss(p, b)[0]))(pp_params, batch)
assert abs(float(loss_ref) - float(loss_pp)) < 2e-2, \
    (float(loss_ref), float(loss_pp))
gn = float(jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree.leaves(g))))
assert np.isfinite(gn) and gn > 0
assert float(jnp.abs(g["embed"]).max()) > 0          # stage 0
assert float(jnp.abs(g["unembed"]).max()) > 0        # last stage
assert float(jnp.abs(g["layers"]["mlp"]["w_gate"]).max()) > 0
assert abs(pipeline_bubble_fraction(2, 4) - 0.2) < 1e-9
print("OK")
"""
    assert "OK" in run_subprocess(code)
