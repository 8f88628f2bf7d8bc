"""Pipeline parallelism (GPipe schedule) over the `pod` axis.

On the 2-pod mesh, the two pods become two pipeline stages: layers are
split [n_stages, L/n_stages, ...] and sharded over `pod`; microbatches
stream through ticks of a lax.scan; stage boundaries exchange activations
with collective_permute (ppermute). DP/TP/EP keep working *inside* the
island: shard_map is manual only over `pod` (axis_names={"pod"}), so GSPMD
still shards data/model within each stage (sharding.exclude_axes drops
`pod` from the logical rules inside).

Fill/drain bubble = (n_stages - 1) / (n_micro + n_stages - 1) — reported,
not hidden: invalid ticks still execute (masked), exactly like hardware.
Backward flows through ppermute automatically (its transpose is the
reverse permute), so jax.grad of the pipelined loss is 1F1B-equivalent
GPipe-with-recompute when the stage body is rematerialized.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh
from repro.models import layers as L
from repro.models import transformer as tf


def stack_stages(params, n_stages: int):
    """Reshape layer-stacked leaves [L, ...] -> [n_stages, L/n_stages, ...]."""
    def reshape(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])

    out = dict(params)
    out["layers"] = jax.tree.map(reshape, params["layers"])
    return out


def stage_axes(axes, n_stages: int):
    out = dict(axes)
    out["layers"] = jax.tree.map(
        lambda ax: ("pp",) + ax, axes["layers"],
        is_leaf=lambda x: isinstance(x, tuple))
    return out


def make_pp_loss_fn(cfg: tf.TransformerConfig, n_micro: int):
    """Pipelined loss over the `pod` axis. params must be stage-stacked
    (stack_stages); batch as usual {tokens,targets,mask} [B, S]."""

    def loss_fn(params, batch, _cfg=None):
        mesh = sh.current_mesh()
        assert mesh is not None and "pod" in mesh.axis_names, \
            "pipeline mode needs a mesh with a 'pod' axis"
        n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]
        B, S = batch["tokens"].shape
        assert B % n_micro == 0
        mb = B // n_micro

        stage_spec = jax.tree.map(lambda _: P("pod"), params["layers"])
        rest_spec = P()  # embed/unembed/final_ln replicated over pod

        @partial(jax.shard_map, mesh=mesh, axis_names={"pod"},
                 in_specs=({"layers": stage_spec, "embed": rest_spec,
                            "unembed": rest_spec, "final_ln": rest_spec},
                           P(), P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(p, tokens, targets, mask):
            with sh.exclude_axes("pod"):
                stage = jax.lax.axis_index("pod")
                layers = jax.tree.map(lambda x: x[0], p["layers"])
                positions = jnp.arange(S)[None, :]

                def stage_fwd(x):
                    def body(x, lp):
                        x, aux = tf._layer_fwd(cfg, x, lp, positions)
                        return x, aux

                    if cfg.remat:
                        body = jax.checkpoint(
                            body,
                            policy=jax.checkpoint_policies.nothing_saveable)
                    x, auxes = jax.lax.scan(body, x, layers)
                    return x, auxes.mean()

                fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

                def tick(carry, t):
                    x_prev, nll_sum, tok_sum, aux_sum = carry
                    x_recv = jax.lax.ppermute(x_prev, "pod", fwd_perm)
                    m_in = jnp.clip(t - stage, 0, n_micro - 1)
                    tok = jax.lax.dynamic_slice_in_dim(
                        tokens, m_in * mb, mb, axis=0)
                    x0 = L.embed_lookup(p["embed"], tok).astype(
                        jnp.dtype(cfg.activation_dtype))
                    x_in = jnp.where(stage == 0, x0, x_recv)
                    y, aux = stage_fwd(x_in)

                    # last stage computes the loss for its current microbatch
                    m_out = t - (n_stages - 1)
                    mo = jnp.clip(m_out, 0, n_micro - 1)
                    tgt = jax.lax.dynamic_slice_in_dim(
                        targets, mo * mb, mb, axis=0)
                    msk = jax.lax.dynamic_slice_in_dim(
                        mask, mo * mb, mb, axis=0)
                    yn = L.rms_norm(y, p["final_ln"], cfg.norm_eps)
                    nll, cnt = L.xent_loss_chunked(
                        yn, p["unembed"], tgt, msk, chunk=cfg.loss_chunk,
                        vocab_real=cfg.vocab, reduce="sum")
                    valid = ((m_out >= 0) & (m_out < n_micro)
                             & (stage == n_stages - 1)).astype(jnp.float32)
                    mvalid = ((t - stage >= 0) & (t - stage < n_micro))
                    return (y, nll_sum + nll * valid,
                            tok_sum + cnt * valid,
                            aux_sum + aux * mvalid.astype(jnp.float32)), None

                x0 = jnp.zeros((mb, S, cfg.d_model),
                               jnp.dtype(cfg.activation_dtype))
                ticks = jnp.arange(n_micro + n_stages - 1)
                (_, nll_sum, tok_sum, aux_sum), _ = jax.lax.scan(
                    tick, (x0, jnp.float32(0), jnp.float32(0),
                           jnp.float32(0)), ticks)
                # share sums across stages (only the last stage contributed)
                nll_sum = jax.lax.psum(nll_sum, "pod")
                tok_sum = jax.lax.psum(tok_sum, "pod")
                aux_sum = jax.lax.psum(aux_sum, "pod") / (n_stages * n_micro)
                loss = nll_sum / jnp.maximum(tok_sum, 1.0)
                return loss + cfg.aux_loss_weight * aux_sum, aux_sum

        loss, aux = run(params, batch["tokens"], batch["targets"],
                        batch["mask"])
        return loss, {"nll": loss, "aux": aux}

    return loss_fn


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
