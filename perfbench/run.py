"""Run one benchmark cell once on this machine's TPU chips.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON result line last on standard output and exits 0; exits
non-zero with no result when JAX finds no TPU, fewer chips than the cell
asks for, or no program (``src/repro``) beside the benchmark.
"""
import pathlib
import sys
import time

if __name__ == "__main__":
    t_start = time.perf_counter()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from perfbench import harness

    sys.exit(harness.main(t_start=t_start))
