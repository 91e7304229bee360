#!/usr/bin/env python3
"""Readings behind the benchmark's limits, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 11,12,13

Runs the cell once per seed and reads, on the same sampled requests, the
program's numbers compared and the control's: the reference computed with
float8 operands, put in the program's place.  The lower reading of a limit
comes from the program over a dozen seeds, the upper from the control; each
side is judged by the benchmark's own comparison (``correct``).  The
benchmark's own runs (``bench/run_cell.py``) do not run the control.  One
JSON line per seed goes to standard output.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from bench import run_cell

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = run_cell.Cell.load(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell.run(cell, seed, args.seconds, False, control=True)
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "control_correct": run_cell.is_correct(r["control_checks"]),
            "program": r["checks"], "control": r["control_checks"],
            "metrics": r["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
