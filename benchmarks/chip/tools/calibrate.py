"""Readings that set a cell's limit on the widest logit gap.

    python3 benchmarks/chip/tools/calibrate.py --workload <name> \
        --seconds <s> --seeds 1 2 3 ... --control-seeds 1 2 3

One process runs the cell once per seed as the benchmark does (the served
path's readings: the widest and the mean gap of its served tokens), and
for each control seed also reads the control: the reference in float8 in
the program's place, on the same prompts and served tokens.  Prints one
JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import check, run, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    bench = run.load_benchmark()
    cell = run.find(bench["workloads"], args.workload, "workload")
    cfg = run.load_json("configs", cell["config"])
    mix = run.load_json("traffic", cell["traffic"])
    run.configure_cache()
    metrics = run.cell_metrics(bench, cell, trace=False)
    for seed in args.seeds:
        got: dict = {}
        out = run.run_cell(cell, cfg, mix, metrics, seed=seed,
                           seconds=args.seconds, trace=False, compared=got)
        gaps = got["gaps"]
        row = {"seed": seed, "correct": out["correct"],
               "program_max_gap": float(gaps.max()) if gaps.size else None,
               "program_mean_gap": float(gaps.mean()) if gaps.size else None,
               "tokens": int(gaps.size),
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if seed in args.control_seeds and got["served"]:
            low = check.control_gaps(
                cfg, seed, got["served"], mix["check"]["requests"],
                traffic.max_rows(mix), mix["reply_tokens"]["max"])
            row["control_max_gap"] = float(low.max())
            row["control_mean_gap"] = float(low.mean())
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
