"""Train-step factory: grads + AdamW + (optional) grad accumulation, wired
with explicit shardings for AOT lowering and real runs alike."""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state, opt_state_specs


def make_train_step(
    loss_fn: Callable,          # (params, batch) -> (loss, metrics)
    opt_cfg: AdamWConfig,
    *,
    grad_accum: int = 1,
):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    With grad_accum > 1 the batch's leading dim is split into microbatches
    and gradients are averaged with a lax.scan (activation memory / accum).
    """

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            loss, metrics, grads = grads_of(params, batch)
        else:
            def split(x):
                return x.reshape((grad_accum, x.shape[0] // grad_accum) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(acc, mb):
                loss, metrics, grads = grads_of(params, mb)
                acc_g, acc_l = acc
                return (jax.tree.map(jnp.add, acc_g, grads), acc_l + loss), metrics

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32 if not jnp.issubdtype(p.dtype, jnp.complexfloating) else p.dtype), params)
            (gsum, lsum), metrics = jax.lax.scan(body, (zero, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum
            # scan stacks per-microbatch metrics along dim 0; report the
            # average over the whole batch, not just the last microbatch
            metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        new_params, new_opt, stats = adamw_update(grads, opt_state, params, opt_cfg)
        metrics = dict(metrics, loss=loss, **stats)
        return new_params, new_opt, metrics

    return train_step


def _named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if isinstance(s, P) else P()),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def train_state_shardings(
    mesh: Mesh,
    param_specs,
    abstract_params,
    *,
    dp_axes=("data",),
    zero1: bool = True,
) -> dict:
    """``{"params": ..., "opt": ...}`` shardings of the train state, as the
    sharded step takes and returns it: build and restore the state with
    these, so no device ever holds the whole unsharded state."""
    opt_specs = opt_state_specs(param_specs, abstract_params, mesh, dp_axes, zero1)
    return {"params": _named(mesh, param_specs), "opt": _named(mesh, opt_specs)}


def shard_train_step(
    train_step: Callable,
    mesh: Mesh,
    param_specs,
    abstract_params,
    batch_specs,
    *,
    dp_axes=("data",),
    zero1: bool = True,
    donate: bool = True,
):
    """jit the step with explicit in/out shardings (params/opt donated)."""
    state = train_state_shardings(
        mesh, param_specs, abstract_params, dp_axes=dp_axes, zero1=zero1
    )
    in_shardings = (state["params"], state["opt"], _named(mesh, batch_specs))
    out_shardings = (state["params"], state["opt"], None)
    return jax.jit(
        train_step,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        donate_argnums=(0, 1) if donate else (),
    )
