"""Executable CCL primitives (shard_map + ppermute) vs jax.lax references,
on 8 fake host devices in a subprocess (plus inline when the interpreter
itself sees >= 8 devices — the CI multi-device matrix entry)."""
import jax
import numpy as np
import pytest

from helpers import run_multidevice

SCRIPT = """
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.ccl.primitives import (ring_all_reduce, bidir_ring_all_reduce,
                                  compressed_ring_all_reduce,
                                  latency_bound_all_reduce, ring_all_gather,
                                  ring_reduce_scatter)

mesh = make_mesh((8,), ("x",))
x = jnp.arange(8 * 48, dtype=jnp.float32).reshape(8, 48) / 7.0

def psum_ref(x, spec):
    return jax.jit(jax.shard_map(lambda xl: jax.lax.psum(xl, "x"),
                                 mesh=mesh, in_specs=spec,
                                 out_specs=spec))(x)

def check(impl, name):
    def body(xl):
        return impl(xl[0], "x", 8)[None]
    got = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x", None),
                                out_specs=P("x", None)))(x)
    # psum with in/out specs sharded returns the sum replicated per shard
    want = psum_ref(x, P("x", None))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    print(name, "ok")

check(ring_all_reduce, "ring")
check(bidir_ring_all_reduce, "bidir_ring")
check(latency_bound_all_reduce, "recursive_doubling")

# ---- satellite: bidir ring on odd-length / non-p-divisible payloads ----
# (covers the flat.size // 2 split and the _pad_to trailing-pad path)
for shape in ((1,), (7,), (33,), (50,), (5, 7), (2, 3, 5)):
    for dt, tol in ((jnp.float32, 2e-6), (jnp.bfloat16, 0.06)):
        y = jax.random.normal(jax.random.PRNGKey(sum(shape)),
                              (8, *shape)).astype(dt)
        spec = P("x", *([None] * len(shape)))
        got = jax.jit(jax.shard_map(
            lambda yl: bidir_ring_all_reduce(yl[0], "x", 8)[None],
            mesh=mesh, in_specs=spec, out_specs=spec))(y)
        want = psum_ref(y, spec)
        assert got.dtype == y.dtype, (shape, dt)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
print("bidir_ring ragged/bf16 ok")

# ---- satellite: all-gather parity on bf16 + ragged sizes vs lax ----
for n in (3, 17, 48):
    for dt in (jnp.float32, jnp.bfloat16):
        y = jax.random.normal(jax.random.PRNGKey(n), (8, n)).astype(dt)
        got = jax.jit(jax.shard_map(
            lambda yl: ring_all_gather(yl[0], "x", 8).reshape(1, -1),
            mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)))(y)
        want = jax.jit(jax.shard_map(
            lambda yl: jax.lax.all_gather(yl[0], "x").reshape(1, -1),
            mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)))(y)
        assert got.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
print("all_gather bf16/ragged ok")

# ---- satellite: reduce-scatter parity on bf16 + ragged sizes ----
# rank r gets sum over peers of their r-th chunk
for n in (6, 5):
    for dt, tol in ((jnp.float32, 2e-6), (jnp.bfloat16, 0.06)):
        y = jax.random.normal(jax.random.PRNGKey(n), (8, 8, n)).astype(dt)
        got = jax.jit(jax.shard_map(
            lambda yl: ring_reduce_scatter(yl[0], "x", 8)[None],
            mesh=mesh, in_specs=P("x", None, None),
            out_specs=P("x", None)))(y)
        want = jax.jit(jax.shard_map(
            lambda yl: jax.lax.psum_scatter(
                yl[0], "x", scatter_dimension=0, tiled=False)[None],
            mesh=mesh, in_specs=P("x", None, None),
            out_specs=P("x", None)))(y)
        assert got.dtype == y.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
print("reduce_scatter bf16/ragged ok")

# ---- compressed ring all-reduce matches psum within codec tolerance ----
for bits, steps_factor in ((8, 127.0), (4, 7.0)):
    for shape in ((48,), (37,)):
        y = jax.random.normal(jax.random.PRNGKey(bits), (8, *shape))
        got = jax.jit(jax.shard_map(
            lambda yl: compressed_ring_all_reduce(yl[0], "x", 8,
                                                  bits=bits)[None],
            mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)))(y)
        want = psum_ref(y, P("x", None))
        # each of the p-1 accumulate hops re-quantizes: p * absmax / qmax
        bound = 8 * float(jnp.abs(y).max()) / steps_factor
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err <= bound, (bits, shape, err, bound)
        # all ranks must hold the identical dequantized result
        np.testing.assert_array_equal(np.asarray(got)[0],
                                      np.asarray(got)[5])
print("compressed_ring ok")
print("OK")
"""


def test_ccl_primitives_multidevice():
    run_multidevice(SCRIPT, num_devices=8)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs >= 8 devices in-process (the CI "
                           "multi-device matrix entry provides them)")
def test_compressed_ring_inline_multidevice():
    """The compressed ring as it would run in production: no subprocess,
    the interpreter's own devices (CI runs the suite once with
    XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.ccl.primitives import compressed_ring_all_reduce
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("x",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    got = jax.jit(jax.shard_map(
        lambda xl: compressed_ring_all_reduce(xl[0], "x", 8)[None],
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)))(x)
    want = x.sum(axis=0)
    bound = 8 * float(jnp.abs(x).max()) / 127.0
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= bound
