"""Training step: microbatched gradient accumulation (scan), remat'd
model forward, optimizer update.

Overlap notes (DESIGN.md §8): accumulation is a ``lax.scan`` whose carry
is the gradient sum — XLA's latency-hiding scheduler overlaps microbatch
k's DP collectives with k+1's compute; the optimizer update happens once
per step on the accumulated (mean) gradient. Accumulation dtype is
configurable (fp32 default; bf16 for deepseek-v3 so the buffer fits HBM).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.model import Model
from repro.optim import adamw as opt_lib
from repro.train.loss import cross_entropy


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: opt_lib.OptConfig = opt_lib.OptConfig()
    accum_dtype: str = "float32"
    aux_coef: float = 0.01
    grad_compression: str = "none"  # none | int8 (see optim/compress.py)


def make_loss_fn(model: Model, tcfg: TrainConfig):
    cfg = model.cfg

    def loss_fn(params, micro):
        logits, _, aux = model.forward(params, micro)
        loss, metrics = cross_entropy(logits, micro["labels"], cfg.vocab)
        total = loss + tcfg.aux_coef * aux
        metrics = dict(metrics, aux=aux, loss=total)
        return total, metrics

    return loss_fn


def make_train_step(model: Model, tcfg: TrainConfig):
    """Returns train_step(params, opt_state, step, batch) -> (params,
    opt_state, metrics). ``batch`` arrays have a leading (accum,) dim."""
    loss_fn = make_loss_fn(model, tcfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    acc_dt = jnp.dtype(tcfg.accum_dtype)

    def train_step(params, opt_state, step, batch):
        accum = jax.tree.leaves(batch)[0].shape[0]

        def micro_step(gsum, micro):
            (_, metrics), grads = grad_fn(params, micro)
            gsum = jax.tree.map(
                lambda a, g: a + g.astype(acc_dt), gsum, grads
            )
            return gsum, metrics

        gzero = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
        gsum, metrics = jax.lax.scan(micro_step, gzero, batch)
        grads = jax.tree.map(lambda g: g / accum, gsum)
        params, opt_state, gnorm = opt_lib.apply_updates(
            params, grads, opt_state, step, tcfg.opt
        )
        metrics = jax.tree.map(lambda m: m.mean(), metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = opt_lib.lr_at(step, tcfg.opt)
        return params, opt_state, metrics

    def sharded_step(params, opt_state, step, batch):
        # the model places its activations with sharding constraints
        # (sharding.spec.constrain), i.e. it is written for auto axes: on a
        # jax.make_mesh mesh (explicit axes) the step runs with the axes
        # turned auto, and params/opt_state keep their input shardings
        if not jax.sharding.get_abstract_mesh().explicit_axes:
            return train_step(params, opt_state, step, batch)
        specs = jax.tree.map(lambda a: jax.typeof(a).sharding.spec,
                             (params, opt_state))
        out_sharding = (*specs, P())
        return jax.sharding.auto_axes(train_step, out_sharding=out_sharding)(
            params, opt_state, step, batch)

    return sharded_step


def init_train_state(model: Model, tcfg: TrainConfig, key):
    params = model.init(key)
    opt_state = opt_lib.init_opt_state(params, tcfg.opt)
    return params, opt_state
