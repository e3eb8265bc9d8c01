"""The one traffic generator: reads a mix (``bench/mixes/<name>.json``)
and turns it into a schedule from the run's seed.

Every seed gets the same work: the same number of events, the same
multiset of inter-arrival gaps (or offsets from a round tick), and the
same sizes; the seed only orders them, picks the node order and draws
the telemetry values. So seeds differ by as little as two runs of one
seed, and a bound set from one seed holds for the next.

Kinds:

- ``poisson``: an open loop of single-node rounds at ``events_per_s``,
  round-robin over the nodes in a seeded order, with exponential gaps.
- ``fleet_rounds``: an open loop in which every node reports once per
  round; rounds tick every ``round_period_s`` and each node's report
  lands at an exponential offset of mean ``offset_mean_s`` after it.
- ``matrices``: a closed loop of scenario matrices, each with
  ``seeds_per_matrix`` fresh search seeds.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Schedule:
    """An open-loop schedule: event i is due ``offset[i]`` seconds after
    the window opens and carries the ``k[i]``-th round of ``node[i]``."""

    offset: np.ndarray  # (n,) float64, sorted, in [0, seconds)
    node: np.ndarray  # (n,) int64 node index
    k: np.ndarray  # (n,) int64 per-node round index

    def __len__(self) -> int:
        return len(self.offset)


def _rng(*path: int) -> np.random.Generator:
    return np.random.default_rng([int(p) for p in path])


def open_schedule(mix: dict, seed: int, seconds: float, n_nodes: int
                  ) -> Schedule:
    kind = mix["kind"]
    base = _rng(mix["base_seed"])
    if kind == "poisson":
        n = int(round(mix["events_per_s"] * seconds))
        gaps = base.exponential(1.0, n)
        gaps = gaps[_rng(seed, 1).permutation(n)]
        cum = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        offset = seconds * cum / gaps.sum()
        order = _rng(seed, 2).permutation(n_nodes)
        i = np.arange(n)
        return Schedule(offset=offset, node=order[i % n_nodes],
                        k=i // n_nodes)
    if kind == "fleet_rounds":
        period = float(mix["round_period_s"])
        lag = base.exponential(mix["offset_mean_s"], n_nodes)
        ticks = np.arange(0.0, seconds, period)
        offs, nodes, ks = [], [], []
        for r, tick in enumerate(ticks):
            perm = _rng(seed, 3, r).permutation(n_nodes)
            offs.append(tick + lag[perm])
            nodes.append(np.arange(n_nodes))
            ks.append(np.full(n_nodes, r))
        offset, node, k = (np.concatenate(a) for a in (offs, nodes, ks))
        keep = offset < seconds
        order = np.argsort(offset[keep], kind="stable")
        return Schedule(offset=offset[keep][order], node=node[keep][order],
                        k=k[keep][order])
    raise ValueError(f"mix kind {kind!r} is not an open loop")


def matrix_seeds(mix: dict, seed: int, k: int) -> List[int]:
    """The search seeds of the ``k``-th matrix of a closed loop
    (``k = -1`` is the warm-up matrix, never measured)."""
    if mix["kind"] != "matrices":
        raise ValueError(f"mix kind {mix['kind']!r} is not a closed loop")
    n = int(mix["seeds_per_matrix"])
    draw = _rng(seed, 4, k + 1).integers(0, 2**31 - 1, n)
    return [int(s) for s in draw]
