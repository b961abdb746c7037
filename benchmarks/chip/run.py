#!/usr/bin/env python3
"""The benchmark's one command: a training cell on the TPU.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout.  ``<cell>`` is a ``workloads`` entry of
``BENCHMARK.json``.  With ``--trace 0`` the last line of standard output is
the cell's end-to-end metrics; with ``--trace 1`` a short traced window
gives its per-layer metrics and a breakdown.  Either way the result says
whether the step agreed with the plain reference, and the last lines of
standard error give each number compared beside its limit.  Without as many
TPU chips as the cell asks for it exits non-zero and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax
    import harness
    import spec
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.load_cell(args.workload)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
