"""Chip benchmark of the substream-matching system.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU
chips of this machine and prints one JSON result line. Everything a
cell needs is found by name: ``configs/<config>.json`` (the deployment),
``graphs/<family>.py`` (its generator), ``traffic/<mix>.json`` (the
job mix), ``entries/<entry>.py`` (the program entry a mix drives) and
``metrics/<metric>.py`` (one per-layer metric reader each).
"""
