"""Step builders: train_step / prefill_step / serve_step.

These close over a ModelSpec + OptimConfig and are what gets jitted by the
launchers and the dry-run. Distribution enters only through in/out
shardings supplied at jit time plus the shard_hints inside the models.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import OptimConfig
from repro.models.api import ModelSpec
from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.grad_compress import error_feedback_update
from repro.optim.schedules import cosine_schedule

Pytree = Any


def make_train_state(spec: ModelSpec, rng: jax.Array, compress: bool = False):
    params = spec.init(rng)
    state = {"params": params, "opt": adamw_init(params)}
    if compress:
        state["residual"] = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params
        )
    return state


def abstract_train_state(spec: ModelSpec, compress: bool = False):
    params = spec.abstract_params()
    f32like = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), t
    )
    state = {
        "params": params,
        "opt": AdamWState(
            jax.ShapeDtypeStruct((), jnp.int32), f32like(params), f32like(params), f32like(params)
        ),
    }
    if compress:
        state["residual"] = f32like(params)
    return state


def build_train_step(
    spec: ModelSpec,
    optim: OptimConfig,
    accum_steps: int = 1,
) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    Gradient accumulation: the global batch is split into ``accum_steps``
    microbatches via lax.scan (keeps HLO O(1) in accum depth).
    """
    compress = optim.compress_grads

    def train_step(state: Dict[str, Pytree], batch: Dict[str, jax.Array]):
        params = state["params"]

        def split(t):
            # microbatch i takes rows i, i + accum, ...: the batch axis keeps
            # its data sharding and the scanned (leading) axis is unsharded
            B = t.shape[0]
            t = t.reshape(B // accum_steps, accum_steps, *t.shape[1:])
            return jnp.swapaxes(t, 0, 1)

        micro = {k: split(v) for k, v in batch.items()}

        def gfn(p, mb):
            return spec.loss(p, mb)

        zero_g = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params
        )

        def acc_body(carry, mb):
            g_acc, loss_acc = carry
            (loss, metrics), g = jax.value_and_grad(gfn, has_aux=True)(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g
            )
            return (g_acc, loss_acc + metrics["loss"]), ()

        (g_sum, loss_sum), _ = jax.lax.scan(
            acc_body, (zero_g, jnp.float32(0.0)), micro
        )
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps, g_sum)
        loss = loss_sum / accum_steps

        new_state = dict(state)
        if compress:
            grads, new_res = error_feedback_update(grads, state["residual"])
            new_state["residual"] = new_res
        lr = cosine_schedule(optim, state["opt"].step)
        new_params, new_opt, gnorm = adamw_update(optim, state["opt"], grads, lr)
        new_state["params"] = new_params
        new_state["opt"] = new_opt
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   "step": new_opt.step}
        return new_state, metrics

    return train_step


def build_prefill_step(spec: ModelSpec) -> Callable:
    def prefill_step(params, tokens, frontend=None):
        logits, cache = spec.prefill(params, tokens, frontend)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, cache

    return prefill_step


def build_serve_step(spec: ModelSpec) -> Callable:
    """One greedy decode step against the KV/state cache."""

    def serve_step(params, cache, tokens, pos):
        logits, cache = spec.decode_step(params, cache, tokens, pos)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, cache

    return serve_step
