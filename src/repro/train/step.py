"""Distributed train/serve step builders: jit + shardings for a mesh.

train_step = loss -> grad -> (optional int8 grad compression) -> AdamW.
Everything is GSPMD-partitioned from logical axis rules; no shard_map needed
for the baseline path (XLA inserts the reduce-scatter/all-gather schedule
for the ZeRO-3 layout).

Gradient compression (beyond-paper, same spirit — quantize the bandwidth-
bound tensor): gradients are quantized to int8 blockwise *before* the
cross-data-axis reduction, with an error-feedback accumulator kept in the
optimizer state; see train/compress.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.api import Model
from repro.parallel.ctx import activation_sharding
from repro.parallel.sharding import (DEFAULT_RULES, batch_sharding,
                                     cache_shardings, params_shardings,
                                     shard_spec_for)
from repro.train.optimizer import (OptConfig, adamw_init, adamw_update,
                                   state_logical_specs)
from repro.train.compress import compress_grads


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    opt: OptConfig = OptConfig()
    grad_compress_bits: int = 32   # 32 (off) | 8 (int8 + error feedback)


def make_train_fns(model: Model, mesh: Mesh, shape: ShapeConfig,
                   tcfg: TrainStepConfig = TrainStepConfig(),
                   rules=DEFAULT_RULES):
    """Returns (init_fn, train_step, shardings) ready to jit/lower.

    init_fn(key) -> state {params, opt, ef}
    train_step(state, batch) -> (state, metrics)
    """
    specs = model.specs()
    pdefs = model.defs()
    shapes = jax.eval_shape(lambda k: model.init(k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shard = params_shardings(specs, shapes, mesh, rules)

    opt_specs = state_logical_specs(specs, tcfg.opt)
    use_ef = tcfg.grad_compress_bits == 8

    def init_fn(key):
        params = model.init(key)
        opt = adamw_init(params, tcfg.opt)
        state = {"params": params, "opt": opt}
        if use_ef:
            state["ef"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
        return state

    state_shapes = jax.eval_shape(
        init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))

    def spec_of(path_axes, shaped):
        return NamedSharding(
            mesh, shard_spec_for(shaped.shape, path_axes, mesh, rules))

    opt_shard = jax.tree.map(
        spec_of, {"params": specs, "opt": opt_specs,
                  **({"ef": specs} if use_ef else {})},
        {"params": state_shapes["params"], "opt": state_shapes["opt"],
         **({"ef": state_shapes["ef"]} if use_ef else {})},
        is_leaf=lambda x: isinstance(x, tuple))

    def train_step(state, batch):
        with activation_sharding(mesh, rules):
            params = state["params"]

            def loss_fn(p):
                return model.loss(p, batch)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            if use_ef:
                grads, new_ef = compress_grads(grads, state["ef"])
            new_params, new_opt, metrics = adamw_update(
                params, grads, state["opt"], tcfg.opt)
            metrics["loss"] = loss
            new_state = {"params": new_params, "opt": new_opt}
            if use_ef:
                new_state["ef"] = new_ef
            return new_state, metrics

    batch_shardings = {
        k: batch_sharding(mesh, len(v.shape), rules, v.shape)
        for k, v in model.input_specs(shape).items()}

    return init_fn, train_step, {
        "state": opt_shard, "batch": batch_shardings}


def make_decode_fns(model: Model, mesh: Mesh, shape: ShapeConfig,
                    rules=DEFAULT_RULES):
    """Returns (decode_step, shardings) for serving dry-runs/engines."""
    specs = model.specs()
    shapes = jax.eval_shape(lambda k: model.init(k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shard = params_shardings(specs, shapes, mesh, rules)
    in_specs = model.input_specs(shape)
    cache_shard = cache_shardings(in_specs["cache"], mesh, rules)

    def decode_step(params, cache, token, index):
        with activation_sharding(mesh, rules):
            logits, new_cache = model.decode(params, cache, token,
                                             index)[:2]
            return logits, new_cache

    shard = {
        "params": p_shard,
        "cache": cache_shard,
        "token": batch_sharding(mesh, 2, rules,
                                in_specs["token"].shape),
        "index": NamedSharding(mesh, P()),
    }
    return decode_step, shard


def make_prefill_fns(model: Model, mesh: Mesh, shape: ShapeConfig,
                     rules=DEFAULT_RULES):
    specs = model.specs()
    shapes = jax.eval_shape(lambda k: model.init(k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shard = params_shardings(specs, shapes, mesh, rules)
    in_specs = model.input_specs(shape)

    def prefill_step(params, batch):
        with activation_sharding(mesh, rules):
            logits, _, _ = model.forward(params, batch)
            return logits[:, -1:]

    batch_shardings = {k: batch_sharding(mesh, len(v.shape), rules, v.shape)
                       for k, v in in_specs.items()}
    return prefill_step, {"params": p_shard, "batch": batch_shardings}
