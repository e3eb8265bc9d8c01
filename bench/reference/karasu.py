"""Plain reference of one Karasu search (Scheinert et al., arXiv
2308.11792): CherryPick whose expected improvement reads an RGPE
ensemble (Feurer, Letham and Bakshy, arXiv 1802.02219, sections 3-4)
of the target's GP and one support model per other workload, each a
GP on a past search that another tenant ran on the healthy fleet;
vanilla or with Perona's acquisition weighting. One search at a time,
in numpy and scipy.

It follows the program's sequential tuner (``tuning/karasu.py``) over
plain arrays and imports nothing of the program. The support history
is built here from this directory's own CherryPick reference
(``search.py``): ``searches`` past searches per workload, their seeds
and each lane's choice of them drawn from numpy seed sequences
``[36, 0, dataset_seed]`` and ``[36, 1, seed]``; the posterior samples
are standard normals from JAX's threefry (original derivation), drawn
in float32 from ``fold_in(fold_in(fold_in(PRNGKey(0), 37), seed), n)``
at a round with n observations, shape (M + 1, S, max_runs). They are
inputs, like the data: drawn on JAX's default device, the device the
program runs on, because the TPU's float32 ``erf_inv`` and the CPU's
differ in the last bits, and a sample one ulp apart can flip a ranking
comparison.

Each round: S joint samples of each support model at the observed
configurations (posterior covariance + 1e-6 I), S samples of the
target from its leave-one-out posteriors; ranking loss
sum_{j,k} 1[(f_j < f_k) xor (y_j < y_k)]; a support model whose median
loss exceeds the target's 95th percentile is dropped; w_i is the
share of samples model i ranks best, a tie split equally (the
expectation of RGPE's random tie-break); mu = sum w_i mu_i and
sigma^2 = sum w_i^2 sigma_i^2 in the target's units. One objective,
cost under the runtime limit; Karasu's multi-objective mode is left
out. ``dtype`` sets the precision (float64 as configured; float32 is
the control). Besides the trace, a search records each round's peak:
the largest expected improvement over the unseen candidates before
the float32 selection grid, which the program reports too.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np
from scipy.linalg import cho_solve

from bench.reference import search as cherrypick

JITTER = 1e-6


def _generator(*path: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(list(path)))


class Support:
    """A past search's GP, as its joint posterior over the candidates
    in its standardized units."""

    def __init__(self, x, y, x_cand, noise):
        gp = cherrypick.GP(noise, np.float64).fit(x, y)
        ks = gp.kernel(np.asarray(x_cand, np.float64), gp.x)
        self.mean = ks @ gp.alpha
        self.cov = gp.kernel(x_cand, x_cand) - ks @ cho_solve(gp.chol, ks.T)
        self.var = np.clip(np.diag(self.cov), 1e-9, None)


@dataclasses.dataclass
class KarasuTrace(cherrypick.Trace):
    peaks: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class History:
    searches: int
    models: List[Support]  # row w * searches + h

    def rows(self, workload: int, seed: int) -> List[int]:
        n = len(self.models) // self.searches
        pick = _generator(36, 1, seed).integers(0, self.searches, n)
        return [w * self.searches + int(pick[w]) for w in range(n)
                if w != workload]


def history(data: dict, searches: int, dataset_seed: int,
            limit_percentile: float, cfg: dict) -> History:
    """``searches`` CherryPick searches of every workload on the
    healthy fleet, and a support model of each."""
    seeds = _generator(36, 0, dataset_seed).integers(0, 2**31 - 1,
                                                     searches)
    x_all = np.asarray(data["features"], np.float64)
    models = []
    for w in range(len(data["workloads"])):
        limit = cherrypick.runtime_limit(data["runtime"][w],
                                         limit_percentile)
        for seed in seeds:
            tr = cherrypick.search(data, w, int(seed), "cherrypick",
                                   "healthy", limit, cfg)
            y = [c if r <= limit else c * 5.0
                 for c, r in zip(tr.costs, tr.runtimes)]
            models.append(Support(x_all[tr.evaluated], np.asarray(y),
                                  x_all, cfg["noise"]))
    return History(searches=searches, models=models)


_DRAW = {}


def normals(seed: int, n: int, shape) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    if shape not in _DRAW:
        def draw(s, k):
            key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0), 37), s), k)
            return jax.random.normal(key, shape, jnp.float32)
        _DRAW[shape] = jax.jit(draw)
    with jax.enable_x64(), jax.threefry_partitionable(False):
        return np.asarray(_DRAW[shape](np.uint32(seed), np.uint32(n)),
                          np.float64)


def weights(losses: np.ndarray, eligible: np.ndarray) -> np.ndarray:
    k, s = losses.shape
    srt = np.sort(losses, axis=1)
    lo, frac = divmod(95 * (s - 1), 100)
    hi = min(lo + 1, s - 1)
    p95_x200 = 2 * (100 * srt[0, lo] + frac * (srt[0, hi] - srt[0, lo]))
    diluted = 100 * (srt[:, (s - 1) // 2] + srt[:, s // 2]) > p95_x200
    keep = eligible & (~diluted | (np.arange(k) == 0))
    masked = np.where(keep[:, None], losses, np.iinfo(np.int64).max)
    tied = masked == masked.min(axis=0)
    lcm = math.lcm(*range(1, k + 1))
    share = np.where(tied, lcm // tied.sum(axis=0), 0)
    total = share.sum(axis=1)
    return np.where(total == s * lcm, 1.0, total / float(s * lcm))


def search(data: dict, hist: History, workload: int, seed: int,
           variant: str, condition: str, limit: float, cfg: dict,
           dtype=np.float64) -> KarasuTrace:
    """One Karasu search of ``workload`` with the support models its
    seed picks from ``hist``."""
    dt = np.dtype(dtype).type
    runtime, cost = data["runtime"][workload], data["cost"][workload]
    lows = data["lows"][workload]
    x = np.asarray(data["features"], dt)
    n_cand = len(x)
    perona = variant.endswith("+perona")
    ns = cherrypick.normalized_scores(data["scores"][condition],
                                      data["vm"])
    price = np.asarray(data["price"], dt)
    support = [hist.models[r] for r in hist.rows(workload, seed)]
    m, samples, r_max = len(support), cfg["samples"], cfg["max_runs"]
    mean_s = np.stack([s.mean for s in support]).astype(dt)
    var_s = np.stack([s.var for s in support]).astype(dt)
    rng = np.random.default_rng(seed)
    evaluated, costs, runtimes, best_curve, peaks = [], [], [], [], []

    def evaluate(i):
        evaluated.append(i)
        runtimes.append(float(runtime[i]))
        costs.append(float(cost[i]))
        valid = [c for c, r in zip(costs, runtimes) if r <= limit]
        best_curve.append(min(valid) if valid else np.inf)

    for i in rng.choice(n_cand, cfg["n_init"], replace=False):
        evaluate(int(i))
    while len(evaluated) < cfg["max_runs"]:
        n = len(evaluated)
        y = np.asarray([c if r <= limit else c * 5.0
                        for c, r in zip(costs, runtimes)], dt)
        gp = cherrypick.GP(cfg["noise"], dt).fit(x[evaluated], y)
        mu, sigma = gp.predict(x)
        z = normals(seed, n, (m + 1, samples, r_max))[:, :, :n].astype(dt)
        kinv = np.diag(cho_solve(gp.chol, np.eye(n, dtype=dt)))
        f = np.zeros((m + 1, samples, n), dt)
        f[0] = gp.y - gp.alpha / kinv + np.sqrt(1 / kinv) * z[0]
        for i, sup in enumerate(support):
            cov = sup.cov[np.ix_(evaluated, evaluated)].astype(dt)
            chol = np.linalg.cholesky(cov + dt(JITTER) * np.eye(n, dtype=dt))
            f[1 + i] = sup.mean[evaluated].astype(dt) + z[1 + i] @ chol.T
        wrong = ((f[..., :, None] < f[..., None, :])
                 != (y[:, None] < y[None, :]))
        w = weights(wrong.sum(axis=(-2, -1)),
                    np.ones(m + 1, bool)).astype(dt)
        if w[0] != 1.0:
            w_t, w_s = w[0], w[1:]
            mix_mu = (w_t * mu + gp.std * (w_s[:, None] * mean_s).sum(0)
                      + gp.mean * w_s.sum())
            sigma = np.sqrt((w_t * sigma) ** 2 + gp.std ** 2
                            * ((w_s * w_s)[:, None] * var_s).sum(0))
            mu = mix_mu.astype(dt)
            sigma = sigma.astype(dt)
        best = dt(np.min(y))
        ei = cherrypick.expected_improvement(mu, sigma, best, dt(cfg["xi"]),
                                             dt)
        if perona:
            util = np.mean([lows[i] for i in evaluated], axis=0).astype(dt)
            util = util / max(util.sum(), 1e-9)
            wp = np.sum(util * ns.astype(dt), axis=1)
            if cfg["per_dollar"] and any(r <= limit for r in runtimes):
                wp = wp / price
            wp = wp / max(wp.mean(), 1e-9)
            ei = (ei * (1.0 + dt(cfg["strength"]) * (wp - 1.0))).astype(dt)
        ei = np.where(np.isin(np.arange(n_cand), evaluated), -np.inf, ei)
        peaks.append(float(np.max(ei)))
        ei = ei.astype(np.float32).astype(np.float64)
        if np.max(ei) <= 0:
            break
        if (np.max(ei) / max(best, 1e-9) < cfg["ei_threshold"]
                and len(evaluated) >= cfg["n_init"] + 2):
            break
        evaluate(int(np.argmax(ei)))
    return KarasuTrace(evaluated=evaluated, costs=costs, runtimes=runtimes,
                       best_valid_cost=best_curve,
                       search_cost=float(np.sum(costs)), peaks=peaks)
