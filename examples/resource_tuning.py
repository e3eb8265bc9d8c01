"""Paper §IV-D use case: iterative cloud-configuration optimization with
Perona-weighted acquisition — replayed through the batched BO engine.

The scenario matrix (workload x tuner variant x fleet condition) runs
as parallel vmapped GP lanes in one dispatch sharded over every
available device (``repro.optimizer.replay_scenarios``), with
the lane tables *generated inside the compiled program* from
counter-based per-lane seeds (``seeded=True``: the host ships the
compact ``SeededLaneSpec`` instead of materialized tables); every
lane reproduces the sequential CherryPick/Arrow trace exactly, so the
printed results are the paper's comparison at a fraction of the wall
clock (see BENCH_optimizer.json).

    PYTHONPATH=src python examples/resource_tuning.py

Add virtual devices to exercise the mesh on a CPU-only box:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/resource_tuning.py
"""

import time

import numpy as np

from repro.optimizer import (HEALTHY, build_scenarios, drifted_condition,
                             replay_scenarios)
from repro.optimizer.scenarios import SOLO_VARIANTS as VARIANTS
from repro.tuning.perona_weights import fingerprint_machine_scores
from repro.tuning.scout import VM_TYPES, ScoutDataset, WORKLOAD_NAMES


def main():
    ds = ScoutDataset(seed=0)
    print(f"scout-like dataset: {len(ds.workloads)} workloads x "
          f"{len(ds.configs)} configs = "
          f"{len(ds.workloads) * len(ds.configs)} runs")

    print("fingerprinting the 9 AWS machine types (540 executions)...")
    scores = fingerprint_machine_scores(VM_TYPES, runs_per_type=10,
                                        epochs=40)

    # fleet conditions: healthy, plus a degraded fleet derived from
    # the drift analytics of a simulated c4 fleet losing cpu quality
    # (the same condition BENCH_optimizer.json tracks)
    degraded = drifted_condition(
        ("c4.large", "c4.xlarge", "c4.2xlarge"), name="c4-cpu-degraded")

    import jax

    workloads = WORKLOAD_NAMES[:4]
    scens = build_scenarios(ds, workloads=workloads, seeds=(1,),
                            conditions=(HEALTHY, degraded))
    t0 = time.perf_counter()
    traces = replay_scenarios(ds, scens, scores, seeded=True,
                              devices=jax.devices())
    dt = time.perf_counter() - t0
    print(f"replayed {len(scens)} searches "
          f"({len(workloads)} workloads x {len(VARIANTS)} variants x "
          f"2 fleet conditions) in {dt:.2f}s — "
          f"{len(traces)} seeded lanes over "
          f"{len(jax.devices())} device(s)\n")

    by_key = {(s.workload, s.variant, s.condition.name): t
              for s, t in zip(scens, traces)}
    for wl in workloads:
        limit = next(s.limit for s in scens if s.workload == wl)
        print(f"{wl} (runtime limit {limit:.0f}s):")
        for cond in ("healthy", degraded.name):
            for variant in VARIANTS:
                tr = by_key[(wl, variant, cond)]
                best = tr.best_valid_cost[-1]
                cfg = min(
                    ((c, co) for c, co, r in
                     zip(tr.evaluated, tr.costs, tr.runtimes)
                     if r <= limit),
                    key=lambda x: x[1], default=(None, np.inf))[0]
                tag = f"{variant:18s} [{cond}]"
                if cfg is not None:
                    print(f"  {tag:38s} best=${best:.4f} "
                          f"({cfg.vm_type} x{cfg.count} | "
                          f"search ${tr.search_cost:.2f}, "
                          f"{len(tr.evaluated)} runs)")
                else:
                    print(f"  {tag:38s} no valid config found")
        print()


if __name__ == "__main__":
    main()
