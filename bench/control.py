"""Readings for the limits of ``correct``: the program's numbers and the
control's, per seed, at the cell's own size and load.

    python3 bench/control.py --workload region1024.steady --seconds 10 \
        --seeds 11 12 13

Each seed is one full run of the cell (set-up, window, check) with the
check's control switched on: the reference in the nearest lower
precision (bfloat16 scoring for float32, float32 searches for float64)
takes the program's place in the comparison, so the control reads the
same numbers against the same limits and has to come out not correct.
The benchmark's own runs never run the control. Prints one JSON line
per seed.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    from bench import run as harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench, cell, config, mix = harness.find_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    for seed in args.seeds:
        cfg = copy.deepcopy(config)
        cfg["check"]["control"] = True
        out, run = harness.run_cell(ROOT, bench, cell, cfg, mix, seed=seed,
                                    seconds=args.seconds, trace=False)
        values = {k: v for k, v in run.values.items()
                  if isinstance(v, (int, float))}
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"], "values": values,
                          "metrics": out["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
