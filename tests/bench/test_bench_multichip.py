"""The 2x2 cell's path on four virtual CPU devices: the sharded step agrees
with the reference, and the step with the exchange between chips left out
turns ``correct`` false."""
import json
import os
import subprocess
import sys
import textwrap

from bench_fixtures import REPO

SCRIPT = textwrap.dedent("""
    import json, sys, tempfile, time
    sys.path.insert(0, {tests!r})
    import bench_fixtures as bf
    harness, spec = bf.import_harness()
    import faults
    from repro.train.step import make_train_step
    cell = spec.load_cell("tiny-gqa-windowed.train.dp2tp2.s64",
                          bf.make_root(tempfile.mkdtemp()))
    out = {{}}
    for name, make in [("sound", None),
                       ("no_exchange", faults.no_exchange(make_train_step))]:
        res = harness.run(cell, 2 ** 32 + 3, 0.3, False, t_start=time.time(),
                          require_tpu=False, make_step=make)
        out[name] = [res["correct"], res["device"]["count"], res["check"]]
    print("RESULT " + json.dumps(out))
""")


def test_sharded_step_and_no_exchange_fault_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c",
         SCRIPT.format(tests=os.path.dirname(os.path.abspath(__file__)))],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["sound"][:2] == [True, 4], out["sound"]
    assert out["no_exchange"][0] is False, out["no_exchange"]
