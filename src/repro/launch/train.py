"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 50 --batch 8 --seq 128

``--devices N`` builds an (N/d, d) host-device mesh (set before jax import)
so the pjit path — planner shardings, EP shard_map, ZeRO-1 — runs on CPU
exactly as it would on the production mesh.
"""
import argparse
import os
import sys


def _preparse_devices() -> int:
    for i, a in enumerate(sys.argv):
        if a == "--devices" and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return 1


_N_DEV = _preparse_devices()
if _N_DEV > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_N_DEV} "
        + os.environ.get("XLA_FLAGS", ""))

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.checkpoint.io import save_checkpoint  # noqa: E402
from repro.configs import ARCHS, get_config, smoke_config  # noqa: E402
from repro.core.types import MeshConfig, TrainConfig  # noqa: E402
from repro.data.pipeline import make_batches  # noqa: E402
from repro.data.stubs import audio_frames, vision_patches  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.transformer import init_params  # noqa: E402
from repro.optim.adamw import init_opt_state  # noqa: E402
from repro.parallel.planner import make_ctx, param_specs  # noqa: E402
from repro.train.step import make_train_step  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=10,
                       total_steps=args.steps, remat=False)

    mesh = ctx = None
    if args.devices > 1:
        d = args.model_axis
        mcfg = MeshConfig(shape=(args.devices // d, d))
        mesh = make_mesh(mcfg.shape, mcfg.axis_names)
        ctx = make_ctx(mesh, mcfg, remat=False)
        print(f"mesh: {dict(zip(mcfg.axis_names, mcfg.shape))}")

    key = jax.random.PRNGKey(tcfg.seed)
    params = init_params(cfg, key)
    if mesh is not None:
        specs = param_specs(cfg, mcfg)
        params = jax.device_put(params, jax.tree.map(
            lambda sp: NamedSharding(mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P)))
    opt = init_opt_state(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"vocab={cfg.vocab_size} layers={cfg.num_layers}")

    step_fn = jax.jit(make_train_step(cfg, tcfg, ctx), donate_argnums=(0, 1))
    batches = make_batches(cfg, args.batch, args.seq, seed=tcfg.seed)
    context = None
    if cfg.is_encoder_decoder:
        context = jnp.asarray(audio_frames(cfg, args.batch))
    elif cfg.cross_attn_period:
        context = jnp.asarray(vision_patches(cfg, args.batch))

    t0 = time.time()
    tokens_seen = 0
    for i, batch in zip(range(args.steps), batches):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        if context is not None:
            b["context"] = context
        params, opt, m = step_fn(params, opt, b)
        tokens_seen += args.batch * args.seq
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            print(f"step {i:5d} loss={float(m['loss']):.4f} "
                  f"ce={float(m['ce']):.4f} lr={float(m['lr']):.2e} "
                  f"gnorm={float(m['grad_norm']):.2f} "
                  f"tok/s={tokens_seen/max(dt,1e-9):,.0f}")
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, args.steps, params, opt)
        print(f"checkpoint: {path}")


if __name__ == "__main__":
    main()
