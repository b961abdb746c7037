"""Collective (decomposed) matmul + executable 2D-torus AR: equivalence
with the bulk-collective forms on a multi-device host platform."""
from helpers import run_multidevice

SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.collective_matmul import ag_matmul, matmul_rs
from repro.ccl.primitives import torus2d_all_reduce
from repro.launch.mesh import make_mesh

P_ = 4
mesh = make_mesh((P_,), ("x",))
key = jax.random.PRNGKey(0)
M, K, N = 8 * P_, 16, 12 * P_
x = jax.random.normal(key, (M, K))
w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) * 0.3

# --- ag_matmul: x row-sharded, w col-sharded -> y col-sharded ---
def body_ag(xl, wl):
    return ag_matmul(xl, wl, "x", P_)
y = jax.jit(jax.shard_map(body_ag, mesh=mesh,
                          in_specs=(P("x", None), P(None, "x")),
                          out_specs=P(None, "x")))(x, w)
np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4)
print("ag_matmul ok")

# --- matmul_rs: x contraction-sharded, w row-sharded -> y row-sharded ---
K2 = 16 * P_
x2 = jax.random.normal(jax.random.fold_in(key, 2), (M, K2))
w2 = jax.random.normal(jax.random.fold_in(key, 3), (K2, N)) * 0.3
def body_rs(xl, wl):
    return matmul_rs(xl, wl, "x", P_)
y2 = jax.jit(jax.shard_map(body_rs, mesh=mesh,
                           in_specs=(P(None, "x"), P("x", None)),
                           out_specs=P("x", None)))(x2, w2)
np.testing.assert_allclose(np.asarray(y2), np.asarray(x2 @ w2), atol=1e-4)
print("matmul_rs ok")

# --- 2D-torus dimension-ordered all-reduce on a (2,2) mesh ---
mesh2 = make_mesh((2, 2), ("r", "c"))
z = jnp.arange(4 * 10, dtype=jnp.float32).reshape(4, 10)
def body_t(zl):
    return torus2d_all_reduce(zl[0], "r", "c", 2, 2)[None]
got = jax.jit(jax.shard_map(
    body_t, mesh=mesh2, in_specs=P(("r", "c"), None),
    out_specs=P(("r", "c"), None)))(z)
want = jnp.broadcast_to(z.sum(0), (4, 10))
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
print("torus2d ok")
print("OK")
"""


def test_collective_matmul_and_torus_ar():
    run_multidevice(SCRIPT, num_devices=4)
