"""AdamW with global-norm clipping.  Optimizer states are plain pytrees so
the ZeRO-1 planner (repro.parallel.planner.zero1_spec) can shard them over
the data axis — the survey's DP All-Reduce becomes Reduce-Scatter +
All-Gather, reducing gradient-sync traffic per device by (dp-1)/dp."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import TrainConfig


def init_opt_state(params: Any) -> Dict[str, Any]:
    # zeros_like keeps each parameter's sharding (no state lands whole on
    # one device of a mesh)
    zeros = lambda p: jnp.zeros_like(p, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def adamw_update(params: Any, grads: Any, state: Dict[str, Any],
                 tcfg: TrainConfig, lr: jax.Array
                 ) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, tcfg.grad_clip / jnp.maximum(gnorm, 1e-9))
    step = state["step"] + 1
    b1, b2 = tcfg.beta1, tcfg.beta2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * jnp.square(g)
        update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + tcfg.eps)
        update = update + tcfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * update).astype(p.dtype), \
            m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_state = {
        "m": jax.tree.unflatten(treedef, [o[1] for o in out]),
        "v": jax.tree.unflatten(treedef, [o[2] for o in out]),
        "step": step,
    }
    return new_params, new_state, {"grad_norm": gnorm}
