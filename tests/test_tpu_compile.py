"""Compile rehearsals for a TPU v5e, from a host without one.

Each test compiles a program of the main path at published widths for a
described ``v5e:2x2`` topology: nothing runs, but the chip's compiler
refuses what it would refuse on the chip (tiling, VMEM, HBM, collectives).
The topology is described inside a module fixture, never at import, and
the tests skip where it cannot be described (no TPU compiler installed).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.ccl.primitives import IMPLEMENTATIONS, make_all_reduce
from repro.configs import get_config
from repro.core.types import MeshConfig, TrainConfig
from repro.kernels.compress.ops import dequantize, quantize
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.ssd_scan.ops import fits as ssd_fits
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.launch.mesh import make_mesh
from repro.models.ssm import init_mamba, mamba_forward
from repro.models.transformer import init_params
from repro.optim.adamw import init_opt_state
from repro.parallel.planner import batch_specs, make_ctx, param_specs
from repro.train.step import make_train_step

V5E_HBM_BYTES = 15.75 * 2 ** 30  # usable HBM of one v5e chip
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kwargs):
    return jax.jit(fn, **kwargs).lower(*args).compile()


def _assert_kernel(compiled):
    # the Mosaic-compiled kernel, not the interpreter's plain HLO
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen2_widths(one_chip):
    cfg = get_config("qwen2-0.5b")
    b, s, d = 8, 1024, cfg.resolved_head_dim
    q = _shape((b, cfg.num_heads, s, d), BF16, one_chip)
    kv = _shape((b, cfg.num_kv_heads, s, d), BF16, one_chip)
    _assert_kernel(_compile(lambda q_, k_, v_: flash_attention(q_, k_, v_),
                            q, kv, kv))


def _kernel_op_names(compiled):
    """The ``op_name`` of every Mosaic kernel of a compiled program."""
    return [m.group(1) for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    """The SSD forward and backward kernels at the mamba2 cell's shapes:
    B 16, L 2048, H 24, P 64, N 128, chunk 256."""
    cfg = get_config("mamba2-130m")
    b, l, h, n = 16, 2048, cfg.ssm_num_heads, cfg.ssm_state
    f32 = jnp.float32
    args = (_shape((b, l, h, cfg.ssm_head_dim), f32, one_chip),
            _shape((b, l, h), f32, one_chip), _shape((h,), f32, one_chip),
            _shape((b, l, n), f32, one_chip), _shape((b, l, n), f32, one_chip))
    assert ssd_fits(args[0].shape, n, 256)
    _assert_kernel(_compile(lambda *a: ssd_scan(*a, chunk=256), *args))

    def loss(*a):
        y, last = ssd_scan(*a, chunk=256)
        return jnp.sum(y * y) + jnp.sum(last)

    grad = _compile(jax.grad(loss, argnums=range(5)), *args)
    assert len(_kernel_op_names(grad)) == 2   # forward, then backward


def test_mamba2_layer_kernels_carry_ssd_scope(one_chip):
    """A mamba2-130m layer's gradient runs the scan through the kernels,
    whose op names keep the ``ssd`` scope that ``ssd_ms`` reads, forward
    and backward."""
    cfg = get_config("mamba2-130m")
    p = jax.tree.map(lambda s: _shape(s.shape, s.dtype, one_chip),
                     jax.eval_shape(lambda k: init_mamba(k, cfg, jnp.float32),
                                    jax.random.PRNGKey(0)))
    x = _shape((2, 1024, cfg.d_model), jnp.float32, one_chip)
    grad = _compile(jax.grad(lambda p_, x_: jnp.sum(
        mamba_forward(p_, cfg, x_) ** 2)), p, x)
    names = _kernel_op_names(grad)
    assert names and all(re.search(r"[/(]ssd[/)]", n) for n in names), names
    assert {"transpose(" in n for n in names} == {True, False}, names


def test_moe_gmm_compiles_at_dbrx_widths(one_chip):
    cfg = get_config("dbrx-132b")
    e = cfg.num_experts // 4  # 4-way expert parallelism: 4 experts a chip
    x = _shape((e, 512, cfg.d_model), BF16, one_chip)
    w = _shape((e, cfg.d_model, cfg.moe_d_ff), BF16, one_chip)
    _assert_kernel(_compile(lambda x_, w_: moe_gmm(x_, w_), x, w))


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_compiles_for_ragged_rows(one_chip, bits):
    # 1000 x 259 floats flatten to 1012 rows of 256: not a multiple of 8
    x = _shape((1000, 259), jnp.float32, one_chip)

    def roundtrip(x_):
        q, scales, shape = quantize(x_, bits=bits)
        return dequantize(q, scales, shape)

    _assert_kernel(_compile(roundtrip, x))


def test_qwen2_train_step_fits_one_v5e(one_chip):
    """Full-width qwen2-0.5b train step (f32 params + AdamW state) at the
    launcher's batch 8 x seq 128 fits one chip's HBM."""
    cfg = get_config("qwen2-0.5b")
    tcfg = TrainConfig(remat=False)
    place = lambda tree: jax.tree.map(
        lambda s: _shape(s.shape, s.dtype, one_chip), tree)
    params = place(jax.eval_shape(lambda k: init_params(cfg, k),
                                  jax.random.PRNGKey(0)))
    opt = place(jax.eval_shape(init_opt_state, params))
    batch = {k: _shape((8, 128), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    compiled = _compile(make_train_step(cfg, tcfg), params, opt, batch,
                        donate_argnums=(0, 1))
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total <= V5E_HBM_BYTES, total / 2 ** 30
    # the blocks' named scopes reach the fusions the chip runs
    fusions = [l for l in compiled.as_text().splitlines() if " fusion(" in l]
    for scope in ("attention", "ffn", "vocab", "optimizer"):
        assert any(re.search(rf'op_name="[^"]*[/(]{scope}[/)]', l)
                   for l in fusions), scope


def test_qwen2_train_step_compiles_on_2x2_mesh(topo):
    """The (data=2, model=2) train step of ``chip_smoke.py --chips 4``:
    sharded parameters and optimizer state, gradients all-reduced."""
    cfg = get_config("qwen2-0.5b")
    tcfg = TrainConfig(remat=False)
    mcfg = MeshConfig(shape=(2, 2))
    mesh = make_mesh(mcfg.shape, mcfg.axis_names, devices=topo.devices)
    specs = param_specs(cfg, mcfg)
    params = jax.tree.map(
        lambda s, sp: _shape(s.shape, s.dtype, NamedSharding(mesh, sp)),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)),
        specs)
    moments = jax.tree.map(
        lambda p: _shape(p.shape, jnp.float32, p.sharding), params)
    opt = {"m": moments, "v": moments,
           "step": _shape((), jnp.int32, NamedSharding(mesh, P()))}
    bspec = batch_specs(mcfg)
    batch = {k: _shape((8, 128), jnp.int32, NamedSharding(mesh, bspec[k]))
             for k in ("tokens", "labels")}
    compiled = _compile(make_train_step(cfg, tcfg, make_ctx(mesh, mcfg,
                                                            remat=False)),
                        params, opt, batch, donate_argnums=(0, 1))
    assert "all-reduce" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes <= V5E_HBM_BYTES


@pytest.mark.parametrize("impl", sorted(IMPLEMENTATIONS))
def test_all_reduce_compiles_on_2x2(topo, impl):
    """Each executable all-reduce at a 16 MiB per-chip payload."""
    mesh = make_mesh((4,), ("x",), devices=topo.devices)
    x = _shape((4, 1 << 22), jnp.float32, NamedSharding(mesh, P("x", None)))
    fn = make_all_reduce(impl, mesh, "x")
    assert "collective-permute" in _compile(fn, x).as_text()
