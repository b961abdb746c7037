"""MoE: routing invariants, dense-vs-EP equivalence (single- and
multi-device), decode-vs-train path agreement."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from helpers import run_multidevice
from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod


def _cfg():
    return smoke_config("dbrx-132b")


def test_route_weights_normalized():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (4, 8, cfg.d_model))
    ids, w, aux = moe_mod.route(p, cfg, x)
    assert ids.shape == (4, 8, cfg.top_k)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert float(aux) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz, =1 balanced


@given(seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_dense_moe_is_convex_combination(seed):
    """moe_dense output must be inside the convex hull of expert outputs:
    ||y|| <= max_e ||ffn_e(x)|| per token (plus shared experts)."""
    cfg = dataclasses.replace(_cfg(), num_shared_experts=0)
    key = jax.random.PRNGKey(seed)
    p = moe_mod.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, cfg.d_model))
    y, _ = moe_mod.moe_dense(p, cfg, x)
    xt = x.reshape(-1, cfg.d_model)
    all_e = moe_mod._expert_ffn(
        p, cfg, jnp.broadcast_to(xt, (cfg.num_experts, *xt.shape)))
    max_norm = jnp.linalg.norm(all_e, axis=-1).max(axis=0)
    y_norm = jnp.linalg.norm(y.reshape(-1, cfg.d_model), axis=-1)
    assert bool((y_norm <= max_norm + 1e-4).all())


EP_SCRIPT = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod
from repro.parallel.planner import ParallelCtx

cfg = dataclasses.replace(smoke_config("dbrx-132b"), num_shared_experts=0)
mesh = make_mesh((2, 2), ("data", "model"))
ctx = ParallelCtx(mesh=mesh, data_axes=("data",), model_axis="model",
                  capacity_factor=float(cfg.num_experts))  # no drops
key = jax.random.PRNGKey(0)
p = moe_mod.init_moe(key, cfg, jnp.float32)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model))

dense, _ = moe_mod.moe_dense(p, cfg, x)
ep, _ = jax.jit(lambda p_, x_: moe_mod.moe_ep_train(
    p_, cfg, x_, mesh, "model", ("data",),
    capacity_factor=float(cfg.num_experts)))(p, x)
np.testing.assert_allclose(np.asarray(ep), np.asarray(dense), atol=2e-5)
print("train ok")

xd = x[:, :1, :]
dense_d, _ = moe_mod.moe_dense(p, cfg, xd)
ep_d, _ = jax.jit(lambda p_, x_: moe_mod.moe_ep_decode(
    p_, cfg, x_, mesh, "model", ("data",),
    capacity_factor=float(cfg.num_experts)))(p, xd)
np.testing.assert_allclose(np.asarray(ep_d), np.asarray(dense_d), atol=2e-5)
print("decode ok")

ws_d, _ = jax.jit(lambda p_, x_: moe_mod.moe_ep_decode_ws(
    p_, cfg, x_, mesh, "model", ("data",),
    capacity_factor=float(cfg.num_experts)))(p, xd)
np.testing.assert_allclose(np.asarray(ws_d), np.asarray(dense_d), atol=2e-5)
print("ws decode ok")
print("OK")
"""


def test_ep_matches_dense_multidevice():
    """All-to-All EP train path and All-Reduce EP decode path both match
    the dense oracle on a 2x2 mesh (capacity high enough for no drops)."""
    run_multidevice(EP_SCRIPT, num_devices=4)


def test_capacity_drops_are_bounded():
    """With tiny capacity, output shrinks (dropped tokens) but stays finite
    and within the convex hull bound."""
    cfg = dataclasses.replace(_cfg(), num_shared_experts=0)
    key = jax.random.PRNGKey(1)
    p = moe_mod.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, cfg.d_model))
    mesh = make_mesh((1, 1), ("data", "model"))
    y, _ = moe_mod.moe_ep_train(p, cfg, x, mesh, "model", ("data",),
                                capacity_factor=0.25)
    assert bool(jnp.isfinite(y).all())
