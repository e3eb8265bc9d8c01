"""Seeded fleet telemetry: the fit acquisition, the store's history and
a pool of rounds that the traffic schedule replays.

The round logic is copied from ``repro.fleet.faults.fleet_telemetry``
(one event per (node, round), round k's executions timestamped one day
after round k-1), so that a later change to the program cannot move
the yardstick; the program keeps its own copy. The execution values
come from the program's suite simulator (``fingerprint.runner``): they
are inputs, and :func:`digest` pins them, so a change to the simulator
fails ``tests/bench`` instead of changing the benchmark in silence.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List

import numpy as np

DAY = 86400.0


def machines(n_nodes: int, machine_type: str) -> Dict[str, str]:
    return {f"fleet-{i}": machine_type for i in range(n_nodes)}


def split_by_node(frame, n_nodes: int) -> List[object]:
    """One sub-frame per node, in node-index order (``fleet-<i>``)."""
    code_of = {name: c for c, name in enumerate(frame.machines)}
    codes = np.asarray([code_of[f"fleet-{i}"] for i in range(n_nodes)])
    order = np.argsort(frame.machine_code, kind="stable")
    bounds = np.searchsorted(frame.machine_code[order],
                             np.arange(len(frame.machines) + 1))
    return [frame.select(order[bounds[c]:bounds[c + 1]]) for c in codes]


class FleetTelemetry:
    """Everything a stream run reads, made from the seed in set-up."""

    def __init__(self, config: dict, seed: int):
        from repro.fingerprint.runner import SuiteRunner

        fleet = config["fleet"]
        self.n_nodes = fleet["nodes"]
        fleet_machines = machines(self.n_nodes, fleet["machine_type"])
        fit = config["fit"]
        # the deployment's preprocessor is fitted once, on a fixed
        # acquisition: its statistics (and so the programs' shapes and
        # constants) are the same for every run seed
        self.fit_frame = SuiteRunner(seed=fit["seed"]).run_frame(
            machines(fit["nodes"], fleet["machine_type"]),
            runs_per_type=fit["runs_per_type"],
            stress_fraction=fit["stress_fraction"])
        runner = SuiteRunner(seed=seed)
        hist = config["history"]
        # collectors stamp executions in epoch seconds
        epoch = config["epoch_s"]
        self.history = runner.run_frame(
            fleet_machines, runs_per_type=hist["runs_per_type"],
            stress_fraction=hist["stress_fraction"], t_offset=epoch)
        self.pool_rounds = config["pool_rounds"]
        self.pool = [split_by_node(runner.run_frame(
            fleet_machines, runs_per_type=1,
            t_offset=epoch + DAY * (1 + r)), self.n_nodes)
            for r in range(self.pool_rounds)]

    def event_frame(self, node: int, k: int):
        """The k-th round of ``node``: pool round k mod P, moved on by
        whole pool cycles so that its timestamps follow round k-1's."""
        frame = self.pool[k % self.pool_rounds][node]
        cycles = k // self.pool_rounds
        if cycles == 0:
            return frame
        return dataclasses.replace(
            frame, t=frame.t + cycles * self.pool_rounds * DAY)

    def digest(self) -> str:
        return digest([self.fit_frame, self.history]
                      + [f for rnd in self.pool for f in rnd])


def digest(frames) -> str:
    """sha256 over every column of ``frames``."""
    h = hashlib.sha256()
    for f in frames:
        for name in ("t", "type_code", "machine_code", "stressed",
                     "metrics", "metrics_present", "node_metrics",
                     "node_metrics_present"):
            h.update(np.ascontiguousarray(getattr(f, name)).tobytes())
        h.update("|".join(f.metric_names + f.benchmark_types).encode())
    return h.hexdigest()
