#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1 2 ... 12 --control-seeds 1 2 3 [--out readings.jsonl]

In one process, for every seed: the program's steps 1-3 as a run makes them
(``harness.Trainer``), and the plain reference on the same rows.  For the
control seeds also the control (the program with bfloat16 weights, the
precision below the float32 the configuration states) and the half-batch
fault, each against the same reference.  Prints one JSON line per reading
and, last, the largest reading of the sound runs and the smallest of each
control or fault, per number.  The benchmark's runs do not run this.
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


def readings(cell, seed, devices, variants):
    """{variant: numbers} for one seed; the reference runs once."""
    import jax.numpy as jnp

    import check
    import faults
    import harness
    progs, shapes = {}, None
    for v in variants:
        dtype = jnp.bfloat16 if v == "control" else jnp.float32
        t = harness.Trainer(cell, seed, devices, dtype=dtype)
        t.start()
        progs[v] = t.first_steps(faults.half_batch if v == "half_batch"
                                 else None)
        shapes = t.shapes
        t.free()
    ref = harness.reference_readings(cell, shapes, seed,
                                     progs["program"]["batches"], devices)
    names = check.slice_names(shapes)
    return {v: check.numbers(p, ref, names) for v, p in progs.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    import spec
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} TPU chip(s)")
    devices = devices[:cell.chips]
    worst = {}
    out = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        variants = ["program"] + (["control", "half_batch"]
                                  if seed in args.control_seeds else [])
        for v, nums in readings(cell, seed, devices, variants).items():
            line = {"cell": cell.name, "seed": seed, "variant": v,
                    "device": devices[0].device_kind,
                    "elapsed_s": time.time() - T_START,
                    **{k: x["value"] for k, x in nums.items()},
                    "at": {k: x["at"] for k, x in nums.items()}}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
            for k, x in nums.items():
                pick = max if v == "program" else min
                key = (v, k)
                worst[key] = pick(worst.get(key, x["value"]), x["value"])
    summary = {f"{v}.{k}": x for (v, k), x in sorted(worst.items())}
    print(json.dumps({"cell": cell.name, "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
