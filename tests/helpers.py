"""Shared test utilities, incl. running multi-device checks in a
subprocess (the only place the fake-device XLA flag is allowed)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_multidevice(script: str, num_devices: int = 8,
                    timeout: int = 420) -> str:
    """Run ``script`` in a subprocess with N fake host devices.  The script
    should print 'OK' on success; raises on failure.  The child is pinned
    to the CPU backend: it is a virtual-device check, and on a host with a
    TPU the chip may already belong to another process."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{num_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"multidevice script failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr[-3000:]}")
    assert "OK" in proc.stdout, proc.stdout
    return proc.stdout
