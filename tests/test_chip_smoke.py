"""``chip_smoke.py`` at tiny sizes on the CPU: its phases run end to end and
its checks hold, and the script itself refuses to run without a TPU."""
import importlib.util
import os
import subprocess
import sys

import pytest

from helpers import run_multidevice
from repro.ccl.primitives import IMPLEMENTATIONS
from repro.configs import smoke_config

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                          text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_train_and_decode_phases(chip_smoke):
    cfg = smoke_config("qwen2-0.5b")
    params, losses = chip_smoke.train_phase(cfg, batch=2, seq=32, steps=2)
    assert len(losses) == 2
    tokens = chip_smoke.decode_phase(cfg, params, batch=2)
    assert tokens.shape == (2, chip_smoke.DECODE_NEW)


def test_kernel_phase(chip_smoke):
    chip_smoke.kernel_phase({
        "flash": dict(b=1, h=4, kv=2, s=256, d=64),
        "ssd": dict(b=1, h=2, l=256, p=64, n=128, chunk=128),
        "gmm": dict(e=2, c=128, d=256, f=256),
        "quant": [(16, 256), (3, 100)]})


def test_kernel_shapes_are_published_widths(chip_smoke):
    shapes = chip_smoke.kernel_shapes()
    assert shapes["flash"] == dict(b=8, h=14, kv=2, s=1024, d=64)
    assert (shapes["ssd"]["h"], shapes["ssd"]["p"], shapes["ssd"]["n"]) == \
        (24, 64, 128)
    assert (shapes["gmm"]["d"], shapes["gmm"]["f"]) == (6144, 10752)


FOUR_CHIP = f"""
import importlib.util
import jax
from repro.configs import smoke_config
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
devices = jax.devices()[:4]
cs.multichip_train_phase(smoke_config("qwen2-0.5b"), devices, batch=4,
                         seq=32)
cs.collectives_phase(devices, elems=1000)
print("OK")
"""


def test_four_chip_phases_on_virtual_devices():
    out = run_multidevice(FOUR_CHIP, num_devices=4)
    # every executable all-reduce plus the synthesized schedule was checked
    assert out.count("all_reduce ") == len(IMPLEMENTATIONS) + 1
