"""Train-step factory: microbatched gradient accumulation, remat, QAT.

One compiled step serves every mixed-precision policy: the bits arrays are
part of TrainState (data, not constants).  The global batch is split into
``n_microbatches`` scanned slices; each microbatch's forward/backward remats
through the per-layer checkpoint policy in models/transformer.py, so live
activation memory is O(one microbatch × one layer).

Optional int8 gradient all-reduce with error feedback
(``grad_compression='int8'``) for the pure-DP regime — the whole
value_and_grad runs inside shard_map over the batch axes so the wire
carries int8 codes instead of bf16 gradients (optim/grad_compress.py).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import transformer as tf
from repro.optim import grad_compress


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any
    policy: Any              # bits arrays pytree {group: {slot: (L[,E])}}
    grad_error: Any = None   # int8-compression error feedback (or None)


def init_train_state(cfg, optimizer, key, policy) -> TrainState:
    params = tf.init_params(cfg, key)
    pa = jax.tree.map(jnp.asarray, policy.as_arrays())
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=optimizer.init(params), policy=pa)


def _split_microbatches(batch: Dict, n: int) -> Dict:
    out = {}
    for k, v in batch.items():
        if k == "mrope_positions":                     # (3, B, S) — batch dim 1
            out[k] = v.reshape(3, n, v.shape[1] // n,
                               *v.shape[2:]).transpose(1, 0, 2, 3)
        else:
            out[k] = v.reshape(n, v.shape[0] // n, *v.shape[1:])
    return out


def batch_pspecs(batch: Dict, axis) -> Dict:
    """PartitionSpecs for a data batch: dim0 sharded (mrope: dim1)."""
    return {k: (P(None, axis) if k == "mrope_positions" else P(axis))
            for k in batch}


def make_train_step(cfg, ctx, optimizer, *, loss_fn: Optional[Callable] = None,
                    n_microbatches: int = 1, accum_dtype=jnp.float32,
                    grad_compression: str = "none") -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    accum_dtype: microbatch gradient-accumulator dtype (bf16 halves the
    gradient residency for ≥100B models; f32 default)."""
    loss_fn = loss_fn or tf.loss_fn

    def loss_for_grad(params, policy, mb):
        loss, metrics = loss_fn(params, policy, mb, cfg, ctx)
        return loss, metrics

    def compute_grads(params, policy, batch):
        if n_microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_for_grad, has_aux=True)(params, policy, batch)
            return grads, metrics
        mbs = _split_microbatches(batch, n_microbatches)

        # Per-microbatch fwd+bwd with in-scan gradient accumulation.  (A
        # hoisted-prequantize variant with a checkpointed loss scan was
        # measured and REGRESSED: remat re-gathers the FSDP weights per
        # microbatch either way, and the extra forward pass costs ~33%
        # compute — EXPERIMENTS.md §Perf A4.)
        def body(acc, mb):
            (loss, metrics), grads = jax.value_and_grad(
                loss_for_grad, has_aux=True)(params, policy, mb)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(accum_dtype), acc, grads)
            return acc, metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype),
                             params)
        acc, metrics = jax.lax.scan(body, zeros, mbs)
        grads = jax.tree.map(lambda g: g / n_microbatches, acc)
        metrics = jax.tree.map(lambda m: jnp.mean(m), metrics)
        return grads, metrics

    if grad_compression == "none":
        def train_step(state: TrainState, batch):
            grads, metrics = compute_grads(state.params, state.policy, batch)
            new_params, new_opt = optimizer.update(grads, state.opt_state,
                                                   state.params)
            new_state = state._replace(step=state.step + 1, params=new_params,
                                       opt_state=new_opt)
            metrics = dict(metrics,
                           grad_norm=grad_compress_norm(grads))
            return new_state, metrics
        return train_step

    if grad_compression != "int8":
        raise ValueError(grad_compression)
    if ctx.mesh is None:
        raise ValueError("int8 grad compression needs a mesh")
    if n_microbatches != 1:
        raise ValueError("int8 grad compression path is pure-DP (1 microbatch)")

    # Pure-DP shard_map step: params replicated, batch sharded, int8 wire.
    n_shards = ctx.batch_size
    axis = ctx.batch_spec
    from repro.parallel.context import ParallelContext
    inner_ctx = ParallelContext(mesh=None)    # model runs shard-locally

    def train_step(state: TrainState, batch):
        def body(params, opt_state, policy, errors, local_batch):
            def local_loss(p):
                loss, metrics = loss_fn(p, policy, local_batch, cfg,
                                        inner_ctx)
                return loss, metrics
            (loss, metrics), grads = jax.value_and_grad(
                local_loss, has_aux=True)(params)

            def reduce_leaf(g, e):
                acc = g.astype(jnp.float32) + e
                red = grad_compress.int8_psum(acc.reshape(-1), axis,
                                              n_shards) / n_shards
                red = red.reshape(g.shape)
                return red.astype(g.dtype), acc - red
            ge = jax.tree.map(reduce_leaf, grads, errors)
            grads_r = jax.tree.map(lambda t: t[0], ge,
                                   is_leaf=lambda x: isinstance(x, tuple))
            new_err = jax.tree.map(lambda t: t[1], ge,
                                   is_leaf=lambda x: isinstance(x, tuple))
            new_params, new_opt = optimizer.update(grads_r, opt_state, params)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
            return new_params, new_opt, new_err, metrics

        errors = state.grad_error
        if errors is None:
            errors = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  state.params)
        new_params, new_opt, new_err, metrics = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(), P(), P(), P(), batch_pspecs(batch, axis)),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )(state.params, state.opt_state, state.policy, errors, batch)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt, policy=state.policy,
                               grad_error=new_err)
        return new_state, metrics

    return train_step


def grad_compress_norm(grads) -> jax.Array:
    sq = jax.tree.reduce(
        lambda a, t: a + jnp.sum(jnp.square(t.astype(jnp.float32))),
        grads, jnp.float32(0.0))
    return jnp.sqrt(sq)
