"""Batched Bayesian-optimization replay engine (paper §IV-D at scale).

The sequential reference tuners live in ``repro.tuning`` (CherryPick /
Arrow, one numpy GP search at a time). This package replays *many*
configuration searches as parallel vmapped lanes on device:

- :mod:`repro.optimizer.gp` — batched masked RBF GP (fit + predict as
  pure jnp ops, pinned against ``tuning/gp.py``);
- :mod:`repro.optimizer.acquire` — expected improvement, the §IV-D
  Perona acquisition weighting and the RGPE ensemble of Karasu lanes
  (ranking losses, weights, mixed predictive) as pure array ops;
- :mod:`repro.optimizer.replay` — full BO search loops as one
  ``lax.scan`` over rounds, every lane advanced per round; the lane
  axis optionally sharded over a 1-D device mesh (``common.mesh``),
  bit-identical to the single-device scan;
- :mod:`repro.optimizer.scenarios` — the §IV-D scenario matrix
  (workload x seed x tuner variant x fleet condition) over the scout
  simulator, including degraded-node fleets from ``fleet.drift``;
  ``replay_scenarios`` is the one way to run a matrix: a wide one
  runs as lane blocks, two in flight on one thread, so that on a
  device whose dispatch returns at once (a TPU) lowering and trace
  materialization overlap a block's scan. The seeded
  path (``lane_spec`` / ``replay_seeded``) ships only the compact
  deterministic grid + per-lane ids and re-derives every stochastic
  table cell inside the compiled program from counter-based
  ``fold_in`` keys — bit-identical to the host tables. Karasu lanes
  (``support_history``: other workloads' past searches as RGPE support
  models) run on the host-table path.
"""

from repro.optimizer.replay import (REPLAY_TRACES, BatchReplayResult,
                                    PendingReplay, ReplayConfig,
                                    SeededLaneSpec, replay,
                                    replay_async, replay_seeded,
                                    replay_seeded_async,
                                    traces_from_result,
                                    traces_from_spec)
from repro.optimizer.scenarios import (HEALTHY, SOLO_VARIANTS, VARIANTS,
                                       DeferredFleetCondition,
                                       FleetCondition, Scenario,
                                       SupportHistory,
                                       build_scenarios,
                                       condition_from_drift,
                                       degrade_scores, drifted_condition,
                                       lane_spec, lane_tables,
                                       reference_search,
                                       replay_scenarios,
                                       resolve_condition,
                                       simulate_degraded_fleet,
                                       support_history)

__all__ = [
    "REPLAY_TRACES", "BatchReplayResult", "PendingReplay",
    "ReplayConfig", "SeededLaneSpec", "replay", "replay_async",
    "replay_seeded", "replay_seeded_async", "traces_from_result",
    "traces_from_spec",
    "HEALTHY", "SOLO_VARIANTS", "VARIANTS", "DeferredFleetCondition",
    "FleetCondition", "Scenario", "SupportHistory",
    "build_scenarios", "condition_from_drift", "degrade_scores",
    "drifted_condition", "lane_spec", "lane_tables",
    "reference_search", "replay_scenarios",
    "resolve_condition", "simulate_degraded_fleet", "support_history",
]
