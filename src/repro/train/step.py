"""Jitted training step: fwd -> loss -> bwd -> clip -> AdamW.

The returned function is pure and pjit-able; the launcher supplies
in/out shardings from the planner.  Gradient synchronization across the
data axes falls out of the sharding propagation: with plain DP specs XLA
emits All-Reduce, with ZeRO-1 specs Reduce-Scatter + All-Gather — the
Para.-layer knob (TrainConfig.grad_sync) the survey describes.

The loss runs under the model's ``vocab`` scope and the update (gradient cast,
schedule, clip, AdamW) under ``optimizer``: ``jax.named_scope`` names that
reach the HLO metadata only (see ``models.transformer``)."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.types import ModelConfig, TrainConfig
from repro.models.transformer import encode, forward
from repro.optim.adamw import adamw_update
from repro.optim.schedule import lr_schedule
from repro.train.loss import cross_entropy


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    ctx=None) -> Callable:
    nmb = max(1, tcfg.microbatches)

    def loss_fn(p, batch):
        context = batch.get("context")
        if cfg.is_encoder_decoder:
            context = encode(cfg, p, context, ctx=ctx)
        logits, aux = forward(cfg, p, batch["tokens"], context=context,
                              ctx=ctx)
        with jax.named_scope("vocab"):
            ce = cross_entropy(logits, batch["labels"])
        loss = ce + cfg.router_aux_loss * aux
        return loss, {"ce": ce, "aux": aux}

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def train_step(params: Any, opt_state: Dict[str, Any],
                   batch: Dict[str, jax.Array]):
        if nmb == 1:
            (loss, metrics), grads = grads_of(params, batch)
        else:
            # gradient accumulation: scan over microbatch slices of the
            # batch dim — live activation memory shrinks by ~nmb (§Perf)
            def split(x):
                b = x.shape[0]
                return x.reshape(nmb, b // nmb, *x.shape[1:])

            mbs = {k: split(v) for k, v in batch.items()}

            def body(acc, mb):
                (loss, metrics), g = grads_of(params, mb)
                g_acc, l_acc, m_acc = acc
                g_acc = jax.tree.map(lambda a, b_: a + b_.astype(a.dtype),
                                     g_acc, g)
                return (g_acc, l_acc + loss,
                        jax.tree.map(lambda a, b_: a + b_, m_acc, metrics)), \
                    None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            init = (g0, jnp.zeros((), jnp.float32),
                    {"ce": jnp.zeros(()), "aux": jnp.zeros(())})
            (grads, loss, metrics), _ = jax.lax.scan(body, init, mbs)
            grads = jax.tree.map(lambda g: g / nmb, grads)
            loss = loss / nmb
            metrics = jax.tree.map(lambda m: m / nmb, metrics)

        with jax.named_scope("optimizer"):
            if tcfg.grad_dtype == "bf16":
                # sync-precision cast: halves the DP gradient collective
                # bytes; AdamW re-accumulates in f32
                grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
            lr = lr_schedule(opt_state["step"], tcfg)
            new_params, new_state, opt_metrics = adamw_update(
                params, grads, opt_state, tcfg, lr)
        metrics = dict(metrics, loss=loss, lr=lr, **opt_metrics)
        return new_params, new_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig, ctx=None) -> Callable:
    def eval_step(params, batch):
        context = batch.get("context")
        if cfg.is_encoder_decoder:
            context = encode(cfg, params, context, ctx=ctx)
        logits, _ = forward(cfg, params, batch["tokens"], context=context,
                            ctx=ctx)
        return cross_entropy(logits, batch["labels"])
    return eval_step
