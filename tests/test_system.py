"""End-to-end behaviour: real training run on the synthetic pipeline (loss
must drop well below the uniform baseline), checkpoint round-trip,
serving loop, pipeline parallelism."""
import math
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_multidevice
from repro.checkpoint.io import restore_checkpoint, save_checkpoint
from repro.configs import smoke_config
from repro.core.types import TrainConfig
from repro.data.pipeline import SyntheticLM, make_batches
from repro.models import decode_step, forward, init_cache, init_params
from repro.optim.adamw import init_opt_state
from repro.serve.step import make_serve_step
from repro.train.step import make_train_step


def test_training_learns_synthetic_pattern():
    cfg = smoke_config("qwen2-0.5b")
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=5, total_steps=60,
                       remat=False)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, tcfg))
    batches = make_batches(cfg, batch_size=8, seq_len=64)
    first = last = None
    for i, batch in zip(range(40), batches):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, m = step(params, opt, b)
        if i == 0:
            first = float(m["loss"])
        last = float(m["loss"])
    uniform = math.log(cfg.vocab_size)
    assert first == pytest.approx(uniform, rel=0.2)
    assert last < 0.8 * uniform, f"loss {first}->{last}, uniform {uniform}"


def test_data_pipeline_deterministic():
    ds = SyntheticLM(vocab_size=97, seq_len=32, seed=5)
    a = ds.batch(0, 0, 4)
    b = ds.batch(0, 0, 4)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.batch(0, 4, 4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next tokens
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_checkpoint_roundtrip():
    cfg = smoke_config("starcoder2-3b")
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    opt = init_opt_state(params)
    with tempfile.TemporaryDirectory() as d:
        path = save_checkpoint(d, 7, params, opt, extra={"note": "t"})
        p2, o2, step = restore_checkpoint(path, params, opt)
        assert step == 7
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(o2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serving_greedy_matches_forward_argmax():
    cfg = smoke_config("granite-3-8b")
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    prompt = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    cache = init_cache(cfg, params, 2, 32)
    serve = jax.jit(make_serve_step(cfg))
    # feed the prompt through decode steps, then generate 4 tokens
    tok = None
    for t in range(8):
        tok, logits, cache = serve(params, cache, prompt[:, t:t + 1], t,
                                   key)
    full, _ = forward(cfg, params, prompt)
    np.testing.assert_array_equal(
        np.asarray(tok[:, 0]), np.asarray(jnp.argmax(full[:, -1], -1)))
    # sampled tokens stay inside the true vocab (padding masked)
    for t in range(8, 12):
        tok, _, cache = serve(params, cache, tok, t, key)
        assert int(tok.max()) < cfg.vocab_size


PIPELINE_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import make_pipeline_fn, bubble_fraction

P_STAGES, M, MB, D = 4, 8, 2, 16
mesh = make_mesh((P_STAGES,), ("pipe",))
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (P_STAGES, D, D)) * 0.2

def stage_fn(wi, x):
    return jnp.tanh(x @ wi)

pipe = make_pipeline_fn(stage_fn, mesh, "pipe")
x = jax.random.normal(jax.random.fold_in(key, 1), (M, MB, D))
got = pipe({"w": w}["w"], x)
# sequential reference
ref = x
for s in range(P_STAGES):
    ref = jnp.tanh(ref @ w[s])
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
print("fwd ok")

# autodiff through the pipeline (backward = reverse ppermutes)
def loss(w_, x_):
    return jnp.sum(pipe(w_, x_) ** 2)
g = jax.grad(lambda w_: loss(w_, x))(w)
def loss_ref(w_):
    r = x
    for s in range(P_STAGES):
        r = jnp.tanh(r @ w_[s])
    return jnp.sum(r ** 2)
g_ref = jax.grad(loss_ref)(w)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)
print("grad ok")
assert abs(bubble_fraction(4, 8, 1) - 3/8) < 1e-9
assert abs(bubble_fraction(4, 8, 2) - 3/16) < 1e-9
print("OK")
"""


def test_pipeline_parallelism_multidevice():
    """GPipe pipeline over a 4-stage mesh axis: forward and gradients match
    the sequential model; PTD-P interleave halves the bubble."""
    run_multidevice(PIPELINE_SCRIPT, num_devices=4)


INTERLEAVED_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import interleaved_pipeline_apply

P_, V, M, MB, D = 4, 2, 6, 2, 8
mesh = make_mesh((P_,), ("pipe",))
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (P_, V, D, D)) * 0.3
x = jax.random.normal(jax.random.fold_in(key, 1), (M, MB, D))

def stage_fn(wc, xx):
    return jnp.tanh(xx @ wc)

def body(w_local, x_all):
    return interleaved_pipeline_apply(stage_fn, w_local[0], x_all,
                                      "pipe", P_, V)
got = jax.jit(jax.shard_map(body, mesh=mesh,
                            in_specs=(P("pipe"), P()),
                            out_specs=P()))(w, x)
# sequential reference: virtual stage k = device k%p, chunk k//p
ref = x
for k in range(V * P_):
    ref = jnp.tanh(ref @ w[k % P_, k // P_])
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
print("interleaved fwd ok")

def loss(w_):
    return jnp.sum(jax.shard_map(
        lambda wl, xa: interleaved_pipeline_apply(
            stage_fn, wl[0], xa, "pipe", P_, V),
        mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P())(w_, x) ** 2)
def loss_ref(w_):
    r = x
    for k in range(V * P_):
        r = jnp.tanh(r @ w_[k % P_, k // P_])
    return jnp.sum(r ** 2)
np.testing.assert_allclose(np.asarray(jax.jit(jax.grad(loss))(w)),
                           np.asarray(jax.grad(loss_ref)(w)), atol=1e-4)
print("interleaved grad ok")
print("OK")
"""


def test_interleaved_pipeline_multidevice():
    """PTD-P interleaved schedule (v=2 chunks/device): forward + gradients
    match the sequential virtual-stage composition."""
    run_multidevice(INTERLEAVED_SCRIPT, num_devices=4)
