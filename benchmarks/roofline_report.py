"""Roofline summary rows from the dry-run JSON (the 40-cell table feed
for EXPERIMENTS.md).

The substream-matching kernel's roofline is measured on the chip by the
benchmark (``perfbench/roofline.py``, the ``substream_match_roofline``
metric), not modelled here.
"""
import json
import os


def run(path="dryrun_results.json"):
    rows = []
    if not os.path.exists(path):
        rows.append(("roofline/dryrun", 0.0, "dryrun_results.json missing"))
        return rows
    data = json.load(open(path))
    ok = sum(1 for v in data.values() if "error" not in v)
    rows.append(("roofline/cells_ok", 0.0, f"{ok}/{len(data)}"))
    for v in data.values():
        if "error" in v or v["mesh"] != "16x16":
            continue
        rf = v["roofline"]
        rows.append(
            (
                f"roofline/{v['arch']}/{v['shape']}",
                rf["step_time_lower_bound_s"] * 1e6,
                f"dom={rf['dominant']};frac={rf.get('roofline_fraction', 0):.4f}",
            )
        )
    return rows
