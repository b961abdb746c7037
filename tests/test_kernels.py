"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.causal_conv.ops import causal_conv, causal_conv_skip
from repro.kernels.causal_conv.ops import fits as conv_fits
from repro.kernels.causal_conv.ref import (causal_conv_ref,
                                           causal_conv_skip_ref)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import moe_gmm_ref
from repro.kernels.ssd_scan.ops import fits as ssd_fits
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,sq,sk,d", [
    (1, 2, 2, 128, 128, 64),
    (2, 4, 2, 256, 256, 64),   # GQA group 2
    (1, 8, 1, 256, 512, 128),  # MQA, rectangular
])
@pytest.mark.parametrize("causal,window", [
    (True, None), (False, None), (True, 128),
])
def test_flash_attention_sweep(b, h, kv, sq, sk, d, causal, window, dtype):
    key = jax.random.PRNGKey(b * 100 + h)
    q = jax.random.normal(key, (b, h, sq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, sk, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, sk, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("block", [(64, 64), (128, 128), (128, 64)])
def test_flash_attention_block_shapes(block):
    bq, bk = block
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 2, 256, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 256, 64))
    out = flash_attention(q, k, v, bq=bq, bk=bk)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


# (B, H, L, P, N, Q): three or more chunks, several heads; two heads to a
# 128-lane group (mamba2's head size) or one (jamba's)
SSD_SHAPES = [
    (1, 4, 384, 64, 128, 128),
    (2, 2, 512, 128, 128, 128),
    (1, 4, 768, 64, 128, 256),   # mamba2-130m's chunk and state
]


def _ssd_inputs(b, h, l, p, n, dtype, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key + l + p), 6)
    x = (jax.random.normal(ks[0], (b, l, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.77))
    bb = (jax.random.normal(ks[3], (b, l, n)) * 0.3).astype(dtype)
    cc = (jax.random.normal(ks[4], (b, l, n)) * 0.3).astype(dtype)
    h0 = jax.random.normal(ks[5], (b, h, p, n)) * 0.1
    return x, dt, a, bb, cc, h0


def _rel(got, want):
    got, want = (np.asarray(v, np.float64).ravel() for v in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# The kernels take every product in one bfloat16 pass (as the TPU takes the
# jnp scan's float32 einsums); the oracle runs in float32 on the CPU.
SSD_TOL = 1e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,l,p,n,chunk", SSD_SHAPES)
def test_ssd_scan_sweep(b, h, l, p, n, chunk, dtype):
    """Forward: y and the final state, from a given initial state."""
    x, dt, a, bb, cc, h0 = _ssd_inputs(b, h, l, p, n, dtype)
    assert ssd_fits(x.shape, n, chunk, x.dtype.itemsize)
    y, last = ssd_scan(x, dt, a, bb, cc, chunk=chunk, h0=h0)
    y_ref, last_ref = ssd_scan_ref(x, dt, a, bb, cc, chunk=chunk, h0=h0)
    assert y.dtype == y_ref.dtype and last.dtype == last_ref.dtype
    assert _rel(y, y_ref) < SSD_TOL
    assert _rel(last, last_ref) < SSD_TOL


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,l,p,n,chunk", SSD_SHAPES)
def test_ssd_scan_gradients(b, h, l, p, n, chunk, dtype):
    """Backward: the gradient of every input, x, dt, a, B, C and the
    initial state, against ``jax.vjp`` of the jnp scan, with cotangents on
    both y and the final state."""
    args = _ssd_inputs(b, h, l, p, n, dtype)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)

    def vjp(f):
        out, pull = jax.vjp(
            lambda *v: f(*v[:5], chunk=chunk, h0=v[5]), *args)
        cts = tuple(jax.random.normal(k, o.shape, o.dtype)
                    for k, o in zip(ks, out))
        return pull(cts)

    for name, got, want in zip(("x", "dt", "a", "b", "c", "h0"),
                               vjp(ssd_scan), vjp(ssd_scan_ref)):
        assert got.dtype == want.dtype, name
        assert _rel(got, want) < SSD_TOL, (name, _rel(got, want))


@pytest.mark.parametrize("head_dim, devices, kernel", [
    (64, 1, True),     # mamba2-130m's widths on one device
    (64, 4, False),    # a mesh of several devices keeps the jnp scan
    (48, 1, False),    # heads the kernels' lane groups do not tile
])
def test_mamba_scan_dispatch(head_dim, devices, kernel):
    """``mamba_forward`` stages the platform switch to the SSD kernels only
    for shapes they tile and a program on one device; the convolution
    kernels (d_inner 1,536 and the 128-wide B and C tile at any head size)
    only on one device."""
    from types import SimpleNamespace

    from repro.configs import get_config
    from repro.models.ssm import init_mamba, mamba_forward
    cfg = dataclasses.replace(get_config("mamba2-130m"),
                              ssm_head_dim=head_dim)
    params = jax.eval_shape(lambda k: init_mamba(k, cfg, jnp.float32),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 256, cfg.d_model), jnp.float32)
    ctx = SimpleNamespace(mesh=SimpleNamespace(size=devices))
    jaxpr = str(jax.make_jaxpr(
        lambda p_, x_: mamba_forward(p_, cfg, x_, ctx=ctx))(params, x))
    assert ("ssd_forward" in jaxpr) is kernel
    assert ("conv_forward" in jaxpr) is (devices == 1)
    assert ("platform_index" in jaxpr) is (devices == 1)


# (B, L, C): several slabs of rows and lane blocks; mamba2's B and C width
# at its sequence; then shapes ``fits`` refuses (L not whole sublane tiles,
# C not whole 128-lane blocks), which run the jnp conv
CONV_SHAPES = [
    ((2, 256, 128), jnp.float32, True),
    ((2, 512, 384), jnp.float32, True),
    ((1, 2048, 128), jnp.float32, True),
    ((2, 272, 256), jnp.bfloat16, True),   # 16-row tiles, 16-row slabs
    ((2, 100, 128), jnp.float32, False),
    ((2, 256, 96), jnp.float32, False),
]


@pytest.mark.parametrize("skip", [False, True])
@pytest.mark.parametrize("shape,dtype,tiles", CONV_SHAPES)
def test_causal_conv_against_oracle(shape, dtype, tiles, skip):
    """The conv kernels (forward, and dx, dw, db by ``jax.vjp``) against
    the jnp ``_causal_conv``, where ``fits`` takes the shape; where it
    does not, ``models.ssm.causal_conv`` stages the jnp conv alone.  With
    ``skip``, ``causal_conv_skip`` against ``_causal_conv_skip``: both
    outputs, and dd too, from both outputs' cotangents."""
    from repro.models import ssm
    ks = jax.random.split(jax.random.PRNGKey(shape[1] + shape[2]), 6)
    x = jax.random.normal(ks[0], shape).astype(dtype)
    w = (jax.random.normal(ks[1], (4, shape[2])) * 0.5).astype(dtype)
    b = (jax.random.normal(ks[2], (shape[2],)) * 0.1).astype(dtype)
    dy = jax.random.normal(ks[3], shape).astype(dtype)
    args = (x, w, b)
    kernel, ref_fn, dispatch = causal_conv, causal_conv_ref, ssm.causal_conv
    if skip:
        args += (jax.random.normal(ks[4], (shape[2],)),)
        dy = (dy.astype(jnp.float32), jax.random.normal(ks[5], shape))
        kernel, ref_fn = causal_conv_skip, causal_conv_skip_ref
        dispatch = ssm.causal_conv_skip
    assert conv_fits(shape, 4, x.dtype.itemsize) is tiles
    staged = str(jax.make_jaxpr(dispatch)(*args))
    assert ("conv_forward" in staged) is tiles
    if not tiles:
        jax.tree.map(np.testing.assert_array_equal, dispatch(*args),
                     ref_fn(*args))
        return
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    out, pull = jax.vjp(kernel, *args)
    ref, pull_ref = jax.vjp(ref_fn, *args)
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert got.dtype == want.dtype and _rel(got, want) < tol
    for name, got, want in zip("xwbd", pull(dy), pull_ref(dy)):
        assert got.dtype == want.dtype, name
        assert _rel(got, want) < tol, (name, _rel(got, want))


def test_ssd_scan_state_continuity():
    """Scanning 2 chunks must differ from treating them independently —
    proves the VMEM carry state crosses the chunk boundary."""
    x, dt, a, bb, cc, _ = _ssd_inputs(1, 2, 256, 64, 128, jnp.float32)
    joint, _ = ssd_scan(x, dt, a, bb, cc, chunk=128)
    h1, _ = ssd_scan(x[:, :128], dt[:, :128], a, bb[:, :128], cc[:, :128],
                     chunk=128)
    h2, _ = ssd_scan(x[:, 128:], dt[:, 128:], a, bb[:, 128:], cc[:, 128:],
                     chunk=128)
    assert np.allclose(np.asarray(joint[:, :128]), np.asarray(h1),
                       atol=1e-5)
    assert not np.allclose(np.asarray(joint[:, 128:]), np.asarray(h2),
                           atol=1e-3), "second chunk ignored carried state"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,d,f,blocks", [
    (2, 128, 256, 128, dict()),
    (4, 256, 512, 384, dict(bd=128)),
    (16, 128, 256, 256, dict(bc=64, bf=128, bd=64)),  # dbrx-like E
])
def test_moe_gmm_sweep(e, c, d, f, blocks, dtype):
    key = jax.random.PRNGKey(e * 10 + f)
    x = jax.random.normal(key, (e, c, d), dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (e, d, f)) * 0.05
         ).astype(dtype)
    out = moe_gmm(x, w, **blocks)
    ref = moe_gmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
