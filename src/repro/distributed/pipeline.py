"""GPipe-style pipeline parallelism over the pod axis (differentiable).

Implements the collective-pipeline pattern: shard_map over the 'pod' axis,
each pod holding a contiguous stage of layers; microbatch activations flow
stage-to-stage with collective_permute inside a python loop of
n_micro + n_stages - 1 ticks.  Because ppermute is differentiable, jax.grad
through the whole step yields the reverse pipeline automatically — no
hand-written backward schedule.

Applies to single-scan layouts (dense/MoE/VLM); embed/unembed params are
replicated across stages.  Inter-pod traffic: one (micro_b, seq, d_model)
activation per tick per boundary — the right trade when pod-to-pod ICI is
the scarce link (vs a full-gradient DP all-reduce).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.blocks import block_train
from ..models.config import BlockKind, ModelConfig
from ..models.layers import embed, rmsnorm, unembed


def split_stage_params(params, n_stages: int):
    """Reshape the (L, ...) scanned stack into (n_stages, L/S, ...)."""
    stack = params["groups"][0]
    resh = jax.tree_util.tree_map(
        lambda x: x.reshape((n_stages, x.shape[0] // n_stages) + x.shape[1:]),
        stack)
    out = dict(params)
    out["groups"] = [resh]
    return out


def stage_param_specs(params, n_stages: int, rules):
    """PartitionSpecs: stage stack sharded over 'pod' on dim 0; embed/norm
    replicated."""
    from .sharding import params_pspecs
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    specs = params_pspecs(shapes, rules)

    def add_pod(leaf, spec):
        inner = (list(spec) + [None] * leaf.ndim)[:leaf.ndim - 1]
        return P("pod", *inner)
    specs["groups"] = [jax.tree_util.tree_map(
        add_pod, params["groups"][0], specs["groups"][0])]
    return specs


def pipeline_loss(params, tokens, cfg: ModelConfig, mesh, n_micro: int,
                  rules) -> jnp.ndarray:
    """Pipelined forward+loss; differentiable.  tokens: (B, S) sharded over
    'data' on batch.  Stage stacks sharded over 'pod'."""
    n_stages = mesh.shape["pod"]
    specs = stage_param_specs(params, n_stages, rules)
    data_spec = P(("data",), None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(specs, data_spec),
        out_specs=P(),
        check_vma=False)
    def run(p, toks):
        stage = jax.lax.axis_index("pod")
        stack = jax.tree_util.tree_map(lambda x: x[0], p["groups"][0])
        b, s = toks.shape
        mb = b // n_micro
        micro = toks.reshape(n_micro, mb, s)

        def apply_stage(x):
            def body(h, blk):
                h2, _ = block_train(blk, h, cfg, BlockKind.ATTN)
                return h2, None
            x, _ = jax.lax.scan(body, x, stack)
            return x

        buf = jnp.zeros((mb, s - 1, cfg.d_model),
                        jnp.dtype(cfg.dtype))
        loss_acc = jnp.zeros((), jnp.float32)
        n_ticks = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]
        for t in range(n_ticks):
            feed_idx = min(t, n_micro - 1)
            inject = embed(p["embed"], micro[feed_idx][:, :-1])
            x = jnp.where(stage == 0,
                          inject.astype(buf.dtype), buf)
            x = apply_stage(x)
            # last stage: finalize loss for microbatch t-(n_stages-1)
            out_idx = t - (n_stages - 1)
            if out_idx >= 0:
                tgt = micro[out_idx][:, 1:]

                def _loss(h):
                    h = rmsnorm(p["final_norm"], h, cfg.norm_eps)
                    logits = unembed(p["embed"], h)
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    nll = -jnp.take_along_axis(logp, tgt[..., None],
                                               axis=-1)[..., 0]
                    return jnp.mean(nll)

                loss_acc = loss_acc + jax.lax.cond(
                    stage == n_stages - 1, _loss,
                    lambda h: jnp.zeros((), jnp.float32), x)
            buf = jax.lax.ppermute(x, "pod", perm)
        total = jax.lax.psum(loss_acc / n_micro, "pod")
        total = jax.lax.pmean(total, "data")
        return total

    return run(params, tokens)
