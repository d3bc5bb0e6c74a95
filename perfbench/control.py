"""The control of a cell's output check: the plain reference put in the
program's place, with the weights and thresholds of the admission test
rounded to bfloat16 (the precision below the configured float32). The
check must call it not correct. Prints one line of readings per seed.

    python3 perfbench/control.py --workload <cell> --seeds 11 12 13

The benchmark's own runs never run it. It drives the harness's window
(one job) and check at the cell's own size on this machine's devices.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import types

import ml_dtypes
import numpy as np


def control_entry(program_entry):
    """An entry whose job is the reference in bfloat16, in the program
    entry's processing order."""
    from perfbench import reference

    def job(wl, params, ctx):
        order = (
            reference.blocked_order(wl.src, wl.dst, wl.K)
            if program_entry.ORDER == "blocked" else None
        )
        ans = reference.solve(
            wl.src, wl.dst, wl.weight, wl.n, wl.L, wl.eps, order=order,
            dtype=ml_dtypes.bfloat16,
        )
        if program_entry.ORDER == "blocked":  # the entry returns Part 2 only
            ans.assigned = ans.state = None
        return ans

    return types.SimpleNamespace(ORDER=program_entry.ORDER, job=job)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    import importlib

    from perfbench import harness

    spec = harness.load_spec()
    cell, config, traffic, e2e, layer = harness.cell_parts(spec, args.workload)
    harness._configure_jax(harness.CACHE)
    program_entry = importlib.import_module(f"perfbench.entries.{traffic['entry']}")
    entry = control_entry(program_entry)
    for seed in args.seeds:
        result, _ = harness.run_cell(
            config, traffic, e2e, layer, seed, 0.001, False, time.perf_counter(),
            entry=entry,
        )
        readings = {k: v["value"] for k, v in result["check"].items()}
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": "bfloat16",
            "correct": result["correct"], "readings": readings,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
