"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` prints
``name,us_per_call,derived`` CSV rows.
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter")
    args = ap.parse_args()

    from benchmarks import (
        bench_throughput,
        fig6_size_scaling,
        fig7_real_graphs,
        fig8_parallel_scaling,
        fig9_approximation,
        fig10_blocking,
        fig11_substreams,
        table6_memory,
        roofline_report,
    )

    suites = [
        ("fig6", fig6_size_scaling),
        ("fig7", fig7_real_graphs),
        ("fig8", fig8_parallel_scaling),
        ("fig9", fig9_approximation),
        ("fig10", fig10_blocking),
        ("fig11", fig11_substreams),
        ("table6", table6_memory),
        ("roofline", roofline_report),
        ("throughput", bench_throughput),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, mod in suites:
        if args.only and args.only not in name:
            continue
        try:
            for row in mod.run():
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name},nan,ERROR")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
