"""Readings behind a cell's limits for ``correct``, on the chip.

    python3 bench/control.py --workload cfm_forces_t3 --seeds 1 2 3

For each seed, in one process: the program's first steps against the
reference (the lower readings), the reference computed in bfloat16 in the
program's place (the control), and the reference given half of each bin in
the program's place (the half-batch fault).  Each line of standard output
is one seed's readings as JSON.  The benchmark's own runs do not run this.

``--energy-weight`` trains the program and the reference with that weight
on the energy term instead of the configuration's: at 1.0 it reads how far
the program's energy term, divided by the bin's graph slots, lies from
MACE's, divided by the real graphs (PERF.md, Open questions).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--energy-weight", type=float, default=None)
    args = ap.parse_args(argv)

    cell = harness.Cell.of_benchmark(
        harness.read_json(harness.ROOT / "BENCHMARK.json"), args.workload)
    jax = harness.start_jax()
    if jax.devices()[0].platform != "tpu":
        print("no chip", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    from benchlib import checks
    from benchlib.generator import Pool

    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.traffic['driver']}.py",
        "bench_driver")
    for seed in args.seeds:
        t0 = time.perf_counter()
        mace_cfg, tcfg, _ = driver.program_config(cell.config)
        if args.energy_weight is not None:
            tcfg = dataclasses.replace(tcfg, energy_weight=args.energy_weight)
        pool = Pool(cell.traffic, float(mace_cfg.r_max), seed)
        b = driver.build(mace_cfg, tcfg, pool, seed,
                         cell.traffic.get("order_seed"))
        log, p0, g1, p3 = driver.first_steps(b)
        prog = driver.program_readings(log, p0, g1, p3)
        bins = log.bins[:driver.CHECK_STEPS]
        del b.trainer, log
        gc.collect()
        want = driver.reference_readings(b, pool, bins, seed)
        control = driver.reference_readings(b, pool, bins, seed,
                                            dtype="bfloat16")
        half = [[r[: max(1, len(r) // 2)] for r in step] for step in bins]
        fault = driver.reference_readings(b, pool, half, seed)
        print(json.dumps({
            "seed": seed,
            "program": checks.readings(prog, want),
            "control_bf16": checks.readings(control, want),
            "fault_half_batch": checks.readings(fault, want),
            "losses": {"program": prog["losses"], "reference": want["losses"]},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
        del b, prog, want, control, fault
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
