"""Readings that the correctness limits of a cell are set from.

    python bench/calibrate.py --workload emnist-ltrf.astraea \
        --seeds 101 102 103 --control 3 --out chiprun_out/cal.jsonl

For every seed: set-up as a benchmark run makes it (the same federation,
weights, trainer and checked rounds), then the reference, and the numbers
of ``bench/check.py`` for the program. For the first ``--control`` seeds
also the numbers of the control (the reference in bfloat16, put in the
program's place) and of two faults planted in the reference put in the
program's place: Eq. 6 over half of the mediators, and the first
mediator's answer negated where it is produced. A state left unchanged
reads 1 on both update numbers by
their definition and needs no run. One JSON line per seed.

The benchmark's own runs never run this; it needs the chip the cell asks
for, like a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import registry  # noqa: E402
from bench.run import (check_numbers, checked_setup, reference_rounds,  # noqa: E402
                       require_chip)


def readings(cell: registry.Cell, seed: int, devices, *,
             control: bool) -> dict:
    """The set-up of a benchmark run (``run.checked_setup``), then the
    reference, and for ``control`` the control and the planted faults,
    each against the reference."""
    import jax.numpy as jnp
    times = {}
    t = time.perf_counter()
    s = checked_setup(cell, seed, devices)
    s.trainer = None
    gc.collect()
    times["program_s"] = time.perf_counter() - t

    t = time.perf_counter()
    ref = reference_rounds(cell, s)
    times["reference_s"] = time.perf_counter() - t
    out = {"seed": seed, "program": check_numbers(cell, s, ref)}
    if control:
        t = time.perf_counter()
        out["control"] = check_numbers(
            cell, s, ref, reference_rounds(cell, s, dtype=jnp.bfloat16))
        times["control_s"] = time.perf_counter() - t
        for fault in ("drop_half", "negate_row0"):
            out[fault] = check_numbers(
                cell, s, ref, reference_rounds(cell, s, fault=fault))
    out["times"] = times
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="read the control and the faults on this many of "
                         "the seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    devices = require_chip(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for i, seed in enumerate(args.seeds):
        with jax.default_matmul_precision(
                cell.config["precision"]["matmul"]):
            row = readings(cell, seed, devices, control=i < args.control)
        row["workload"] = cell.name
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
