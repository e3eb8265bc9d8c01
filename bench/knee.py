"""Knee sweep of an open-loop stream cell: the highest offered rate at
which the backlog of due-but-unscored events stops growing.

    python3 bench/knee.py --workload region1024.steady --seconds 10 \
        --rates 500 1000 2000 4000

Runs the cell's served path once per rate in this process (each run
pays its own set-up), with the reference check cut to a few events,
and prints per rate: the backlog's growth over the window (events/s,
a least-squares slope of the backlog sampled every 0.25 s), the p95
latency and the share of events scored by the window's end. The
result goes into the mix file by hand, as a number.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog_slope(due, scored, seconds: float) -> float:
    import numpy as np

    ts = np.arange(0.25, seconds, 0.25)
    backlog = [(due <= t).sum() - (scored <= t).sum() for t in ts]
    return float(np.polyfit(ts, backlog, 1)[0])


def main() -> None:
    import numpy as np

    from bench import run as harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench, cell, config, mix = harness.find_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    for rate in args.rates:
        cfg, m = copy.deepcopy(config), copy.deepcopy(mix)
        cfg["check"]["events"] = 8
        # near and past the knee a node's rounds pile up in one flush:
        # warm the 256-row bucket too, so no compile lands in a window
        cfg["max_rounds_per_flush"] = 26
        m["kind"], m["events_per_s"] = "poisson", rate
        out, run = harness.run_cell(ROOT, bench, cell, cfg, m,
                                    seed=args.seed, seconds=args.seconds,
                                    trace=False)
        due, scored = run.values["due_s"], run.values["scored_s"]
        done = float(np.mean(scored <= args.seconds))
        print(json.dumps({
            "rate": rate,
            "backlog_growth_per_s": backlog_slope(due, scored,
                                                  args.seconds),
            "p95_ms": out["metrics"].get("score_p95_ms", {}).get("value"),
            "scored_by_window_end": done, "correct": out["correct"],
            "flushes": run.counts["flushes"],
            "flush_host_ms": sum(e - s for s, e in run.spans["flush"])
            / max(len(run.spans["flush"]), 1) * 1e3}), flush=True)


if __name__ == "__main__":
    main()
