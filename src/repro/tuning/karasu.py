"""Karasu re-implementation (Scheinert et al., arXiv 2308.11792):
collaborative cluster-configuration search.

A tenant's CherryPick search borrows the profiling runs that other
tenants made of *other* jobs on the same configuration space, through
an RGPE ensemble (Feurer, Letham and Bakshy, "Scalable Meta-Learning
for Bayesian Optimization using Ranking-Weighted Gaussian Process
Ensembles", arXiv 1802.02219, sections 3-4): one GP per past search
(a "support model") and the target's own GP, each weighted by how
often it ranks the target's observations best over posterior samples;
EI then runs on the weighted mixture.

This is the plain sequential float64 reference that the replay
engine's Karasu lanes (``optimizer.replay._rgpe_predict``) are pinned
against, and it follows the same equations:

- a support model is a :class:`tuning.gp.GP` on its past search's
  standardized, constraint-penalized costs (CherryPick's features,
  median-heuristic scales, noise 1e-3), kept as the joint posterior of
  the latent function over every candidate;
- at a round with n observations, S joint samples of each support
  model at the n observed configurations (Cholesky factor of its
  posterior covariance there plus ``RGPE_JITTER`` I) and S samples of
  the target from its leave-one-out posteriors, all from
  ``common.rng.normal_block(stream, seed, n)`` of shape
  (M + 1, S, max_runs): row 0 the target's, row 1 + i support slot
  i's, the first n columns used;
- the ranking loss of a model and sample is
  sum_{j,k} 1[(f_j < f_k) xor (y_j < y_k)];
- w_i is the share of samples in which model i has the lowest loss; a
  support model whose median loss exceeds the 95th percentile of the
  target's loss is dropped first (the paper's guard against weight
  dilution);
- the predictive is mu = sum_i w_i mu_i, sigma^2 = sum_i w_i^2
  sigma_i^2, the support models mapped through the target's
  standardization; with all the weight on the target it is the
  target's posterior unchanged.

Departures from the papers: a tie for the lowest loss splits the
sample's weight equally among the tied models (the expectation of
RGPE's random tie-break); there is a single objective, execution cost
under the runtime limit, as in Perona section IV-D; Karasu's
multi-objective mode is left out; the past searches come from the
seeded Scout simulation, not from Karasu's public-cloud traces.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import numpy as np
from scipy.linalg import cho_solve

from repro.tuning.cherrypick import CherryPick, SearchTrace
from repro.tuning.gp import GP
from repro.tuning.scout import ScoutDataset

#: Diagonal jitter of a support model's posterior covariance at the
#: target's observations (standardized units).
RGPE_JITTER = 1e-6


class SupportModel:
    """A past search's GP as its joint posterior over the candidates
    ``x_cand``, in the search's standardized units."""

    def __init__(self, x_obs: np.ndarray, y_obs: np.ndarray,
                 x_cand: np.ndarray, noise: float = 1e-3):
        gp = GP(noise).fit(x_obs, y_obs)
        ks = gp._k(np.asarray(x_cand, float), gp.X)
        self.mean = ks @ gp.alpha
        self.cov = gp._k(x_cand, x_cand) - ks @ cho_solve(gp.chol, ks.T)
        self.var = np.clip(np.diag(self.cov), 1e-9, None)


def ranking_losses(f: np.ndarray, y: np.ndarray) -> np.ndarray:
    """(K, S) count of misranked ordered pairs of the n observations
    by samples ``f`` (K, S, n) against observed ``y`` (n,)."""
    wrong = (f[..., :, None] < f[..., None, :]) != (y[:, None] < y[None, :])
    return wrong.sum(axis=(-2, -1))


def rgpe_weights(losses: np.ndarray, eligible: np.ndarray) -> np.ndarray:
    """RGPE weights from ranking losses (K, S), row 0 the target's:
    the dilution guard (median and 95th percentile by linear
    interpolation at the exact positions, compared in integers scaled
    by 200), then the share of samples each model ranks best, a tie
    split equally. Shares are counted as integers lcm(1..K) / ties and
    divided once, as the program does."""
    k, s = losses.shape
    srt = np.sort(losses, axis=1)
    lo, frac = divmod(95 * (s - 1), 100)
    hi = min(lo + 1, s - 1)
    p95_x200 = 2 * (100 * srt[0, lo] + frac * (srt[0, hi] - srt[0, lo]))
    diluted = 100 * (srt[:, (s - 1) // 2] + srt[:, s // 2]) > p95_x200
    keep = np.asarray(eligible) & (~diluted | (np.arange(k) == 0))
    masked = np.where(keep[:, None], losses, np.iinfo(np.int64).max)
    tied = masked == masked.min(axis=0)
    lcm = math.lcm(*range(1, k + 1))
    share = np.where(tied, lcm // tied.sum(axis=0), 0)
    total = share.sum(axis=1)
    return np.where(total == s * lcm, 1.0, total / float(s * lcm))


def _normals(seed: int, n: int, shape: Tuple[int, ...]) -> np.ndarray:
    """The round's standard normals. They are inputs, like the data:
    drawn on JAX's default device, the device the replay program runs
    on, because the TPU's float32 ``erf_inv`` and the CPU's differ in
    the last bits, and a sample one ulp apart can flip a ranking
    comparison."""
    from repro.common.rng import (STREAM_RGPE, normal_block, stream_key,
                                  x64_streams)

    with x64_streams():
        return np.asarray(normal_block(stream_key(0, STREAM_RGPE), seed, n,
                                       shape))


class Karasu(CherryPick):
    """CherryPick whose EI reads the RGPE ensemble of the target's GP
    and one support model per slot of ``support``: a past search as
    ``(SearchTrace, runtime limit)``, or None for an empty slot."""

    name = "karasu"

    def __init__(self, dataset: ScoutDataset, runtime_limit_s: float,
                 support: Sequence[Optional[Tuple[SearchTrace, float]]],
                 samples: int = 256, noise: float = 1e-3, **kw):
        super().__init__(dataset, runtime_limit_s, **kw)
        self.samples = samples
        self._col = {c.key: j for j, c in enumerate(dataset.configs)}
        x_cand = np.stack([self._features(c) for c in dataset.configs])
        self.support = []
        for past in support:
            if past is None:
                self.support.append(None)
                continue
            trace, limit = past
            y = np.asarray([c if r <= limit else c * 5.0
                            for c, r in zip(trace.costs, trace.runtimes)])
            x = np.stack([self._features(c) for c in trace.evaluated])
            self.support.append(SupportModel(x, y, x_cand, noise))

    def _predict(self, gp: GP, X: np.ndarray, evaluated, y: np.ndarray):
        mu, sigma = gp.predict(X)
        n, m = len(evaluated), len(self.support)
        z = _normals(self.seed, n, (m + 1, self.samples, self.max_runs))
        idx = [self._col[c.key] for c in evaluated]
        kinv_diag = np.diag(cho_solve(gp.chol, np.eye(n)))
        f = np.zeros((m + 1, self.samples, n))
        f[0] = (gp.y - gp.alpha / kinv_diag
                + np.sqrt(1.0 / kinv_diag) * z[0, :, :n])
        n_cand = len(X)
        mean_s, var_s = np.zeros((m, n_cand)), np.zeros((m, n_cand))
        for i, sm in enumerate(self.support):
            if sm is None:
                continue
            chol = np.linalg.cholesky(sm.cov[np.ix_(idx, idx)]
                                      + RGPE_JITTER * np.eye(n))
            f[1 + i] = sm.mean[idx] + z[1 + i, :, :n] @ chol.T
            mean_s[i], var_s[i] = sm.mean, sm.var
        eligible = np.asarray([True] + [sm is not None
                                        for sm in self.support])
        w = rgpe_weights(ranking_losses(f, np.asarray(y)), eligible)
        if w[0] == 1.0:
            return mu, sigma
        w_t, w_s = w[0], w[1:]
        mix_mu = (w_t * mu + gp.y_std * (w_s[:, None] * mean_s).sum(0)
                  + gp.y_mean * w_s.sum())
        mix_var = ((w_t * sigma) ** 2
                   + gp.y_std ** 2 * ((w_s * w_s)[:, None] * var_s).sum(0))
        return mix_mu, np.sqrt(mix_var)
