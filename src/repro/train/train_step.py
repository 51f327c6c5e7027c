"""Train/serve step builders shared by the trainer and the dry-run."""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .optimizer import AdamW, apply_updates


def make_train_step(model, opt: AdamW) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, loss, metrics)."""

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(model.train_loss, has_aux=True)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss, metrics

    return train_step


def make_grad_accum_train_step(model, opt: AdamW, microbatches: int) -> Callable:
    """Gradient-accumulation variant: splits the batch into ``microbatches``
    sequential micro-steps (scan) before one optimizer update.  Cuts
    activation memory by the same factor at zero extra communication."""

    def train_step(params, opt_state, batch):
        def micro(batch_slice):
            return jax.value_and_grad(model.train_loss, has_aux=True)(params, batch_slice)

        def split(x):
            b = x.shape[0]
            return x.reshape((microbatches, b // microbatches) + x.shape[1:])

        stacked = jax.tree.map(split, batch)

        def body(carry, batch_slice):
            loss_acc, grads_acc = carry
            (loss, _), grads = micro(batch_slice)
            grads_acc = jax.tree.map(lambda a, g: a + g, grads_acc, grads)
            return (loss_acc + loss, grads_acc), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), _ = jax.lax.scan(body, (0.0, zeros), stacked)
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss_sum / microbatches, {}

    return train_step


def make_serve_step(model) -> Callable:
    """(params, cache, tokens, pos) -> (logits, cache)."""

    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return serve_step


def make_prefill_step(model) -> Callable:
    def prefill_step(params, batch):
        return model.prefill(params, batch)

    return prefill_step
