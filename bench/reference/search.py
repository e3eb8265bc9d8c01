"""Plain reference of one §IV-D configuration search: CherryPick
(Alipourfard et al., NSDI'17) and Arrow (Hsu et al., ICDCS'18), each
vanilla or with Perona's acquisition weighting, one search at a time
in numpy and scipy.

It follows the sequential tuners the repository reproduces the paper
with (``tuning/cherrypick.py``, ``tuning/arrow.py``, ``tuning/gp.py``,
``tuning/perona_weights.py``), rewritten over plain arrays so that it
imports nothing of the program: an RBF Gaussian process with median
length scales, expected improvement on the constraint-penalized cost,
float32-rounded selection, and the EI-ratio stop. ``dtype`` sets the
precision of every step (float64 as configured; float32 is the
control).
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np
from scipy.linalg import cho_factor, cho_solve
from scipy.stats import norm

ASPECTS = ("cpu", "memory", "disk", "network")


@dataclasses.dataclass
class Trace:
    evaluated: List[int]  # configuration indices, in order
    costs: List[float]
    runtimes: List[float]
    best_valid_cost: List[float]
    search_cost: float


def runtime_limit(runtimes: np.ndarray, percentile: float) -> float:
    return float(np.percentile(runtimes, percentile))


def score_vectors(scores: dict, vms) -> np.ndarray:
    return np.asarray([[scores.get(vm, {}).get(a, 0.0) for a in ASPECTS]
                       for vm in vms])


def normalized_scores(scores: dict, vms) -> np.ndarray:
    """Per-aspect min-max over machine types, plus 0.1."""
    types = list(scores)
    arr = score_vectors(scores, types)
    lo, hi = arr.min(0), arr.max(0)
    norm_ = (arr - lo) / np.where(hi > lo, hi - lo, 1.0) + 0.1
    row = {m: norm_[i] for i, m in enumerate(types)}
    return np.stack([row.get(vm, np.ones(4)) for vm in vms])


class GP:
    def __init__(self, noise: float, dtype):
        self.noise, self.dt = noise, dtype

    def fit(self, x, y):
        dt = self.dt
        self.x = np.asarray(x, dt)
        y = np.asarray(y, dt)
        self.mean = dt(np.mean(y))
        std = dt(np.std(y))
        self.std = std if std > 1e-12 * max(1.0, abs(self.mean)) else dt(1)
        self.y = (y - self.mean) / self.std
        if len(self.x) < 2:
            self.scales = np.ones(self.x.shape[-1], dt)
        else:
            med = np.median(np.abs(self.x[:, None] - self.x[None]),
                            axis=(0, 1))
            self.scales = np.where(med > 1e-9, med, 1.0).astype(dt)
        k = self.kernel(self.x, self.x) + dt(self.noise) * np.eye(
            len(self.x), dtype=dt)
        self.chol = cho_factor(k)
        self.alpha = cho_solve(self.chol, self.y).astype(dt)
        return self

    def kernel(self, a, b):
        d = (a[:, None, :] - b[None, :, :]) / self.scales
        return np.exp(self.dt(-0.5) * np.sum(d * d, axis=-1)).astype(
            self.dt)

    def predict(self, xs):
        ks = self.kernel(np.asarray(xs, self.dt), self.x)
        mu = ks @ self.alpha
        v = cho_solve(self.chol, ks.T).astype(self.dt)
        var = np.clip(1.0 - np.sum(ks * v.T, axis=1), 1e-9, None)
        return (mu * self.std + self.mean,
                (np.sqrt(var) * self.std).astype(self.dt))


def expected_improvement(mu, sigma, best, xi, dtype):
    imp = (best - mu - xi).astype(dtype)
    z = (imp / np.maximum(sigma, 1e-9)).astype(dtype)
    ei = imp * norm.cdf(z).astype(dtype) + sigma * norm.pdf(z).astype(dtype)
    return np.maximum(ei, 0.0).astype(dtype)


def search(data: dict, workload: int, seed: int, variant: str,
           condition: str, limit: float, cfg: dict,
           dtype=np.float64) -> Trace:
    """One search of ``variant`` over every configuration of
    ``data`` for ``workload`` under ``condition``'s machine scores."""
    dt = np.dtype(dtype).type
    runtime, cost = data["runtime"][workload], data["cost"][workload]
    lows = data["lows"][workload]
    base = np.asarray(data["features"], dt)
    n = len(base)
    scores = data["scores"][condition]
    arrow = variant.startswith("arrow")
    perona = variant.endswith("+perona")
    fp = score_vectors(scores, data["vm"])

    def features(i, evaluated_now):
        if not arrow:
            return base[i]
        if perona:
            low = fp[i]
        elif evaluated_now:
            low = lows[i]
        else:
            low = np.zeros(4)  # candidates: no run observed at start
        return np.concatenate([base[i], np.asarray(low, dt)])

    x_cand = np.stack([features(i, False) for i in range(n)]).astype(dt)
    ns = normalized_scores(scores, data["vm"])
    price = np.asarray(data["price"], dt)
    rng = np.random.default_rng(seed)
    evaluated, costs, runtimes, best_curve = [], [], [], []

    def evaluate(i):
        evaluated.append(i)
        runtimes.append(float(runtime[i]))
        costs.append(float(cost[i]))
        valid = [c for c, r in zip(costs, runtimes) if r <= limit]
        best_curve.append(min(valid) if valid else np.inf)

    for i in rng.choice(n, cfg["n_init"], replace=False):
        evaluate(int(i))
    while len(evaluated) < cfg["max_runs"]:
        y = np.asarray([c if r <= limit else c * 5.0
                        for c, r in zip(costs, runtimes)], dt)
        gp = GP(cfg["noise"], dt).fit(
            np.stack([features(i, True) for i in evaluated]), y)
        mu, sigma = gp.predict(x_cand)
        best = dt(np.min(y))
        ei = expected_improvement(mu, sigma, best, dt(cfg["xi"]), dt)
        if perona:
            util = np.mean([lows[i] for i in evaluated], axis=0).astype(dt)
            util = util / max(util.sum(), 1e-9)
            w = np.sum(util * ns.astype(dt), axis=1)
            if cfg["per_dollar"] and any(r <= limit for r in runtimes):
                w = w / price
            w = w / max(w.mean(), 1e-9)
            ei = (ei * (1.0 + dt(cfg["strength"]) * (w - 1.0))).astype(dt)
        ei = np.where(np.isin(np.arange(n), evaluated), -np.inf, ei)
        ei = ei.astype(np.float32).astype(np.float64)
        if np.max(ei) <= 0:
            break
        if (np.max(ei) / max(best, 1e-9) < cfg["ei_threshold"]
                and len(evaluated) >= cfg["n_init"] + 2):
            break
        evaluate(int(np.argmax(ei)))
    return Trace(evaluated=evaluated, costs=costs, runtimes=runtimes,
                 best_valid_cost=best_curve,
                 search_cost=float(np.sum(costs)))
