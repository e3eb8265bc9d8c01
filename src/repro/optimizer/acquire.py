"""Acquisition functions as pure array ops (paper §IV-D).

Expected improvement mirrors ``tuning.gp.expected_improvement`` and the
Perona acquisition weighting mirrors ``tuning.perona_weights.
PeronaAcquisitionWeighter.__call__`` — both are the numpy references
the parity tests pin against. Inputs arrive precomputed as matrices
(normalized machine-score rows per candidate configuration, observed
utilization per evaluated run), so a weighting step is one
contraction over the 4 aspects per candidate. It is written as a
multiply and sum, not ``@``: on the TPU a float64 dot compiles to a
multi-pass bfloat16 loop (see ``optimizer.gp``).

The RGPE ensemble of Karasu lanes (Feurer, Letham and Bakshy, arXiv
1802.02219, sections 3-4; ``tuning.karasu`` is its sequential
reference) is here too: ranking losses over posterior samples, the
weights they give, and the weighted predictive that feeds
:func:`expected_improvement`. Departures from the paper: a tie for the
lowest loss splits the sample's weight equally among the tied models
(the expectation of the paper's random tie-break), and there is one
objective, the constraint-penalized cost.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax.scipy.stats import norm


def expected_improvement(mu: jnp.ndarray, sigma: jnp.ndarray,
                         best, xi: float = 0.01) -> jnp.ndarray:
    """EI for *minimization*; clipped at 0 (EI is non-negative by
    definition — the clip removes float underflow artifacts)."""
    imp = best - mu - xi
    z = imp / jnp.maximum(sigma, 1e-9)
    ei = imp * norm.cdf(z) + sigma * norm.pdf(z)
    return jnp.maximum(ei, 0.0)


def perona_weight_factors(util: jnp.ndarray, norm_scores: jnp.ndarray,
                          prices: jnp.ndarray, any_valid,
                          strength: float = 0.3,
                          per_dollar: bool = True) -> jnp.ndarray:
    """Multiplicative acquisition factors of the §IV-D weighting.

    ``util`` (4,) mean observed per-aspect utilization of the runs so
    far; ``norm_scores`` (C, 4) normalized fingerprint score vector of
    each candidate's machine type; ``prices`` (C,) on-demand $/h.
    Two-phase prior: capability while no valid configuration is known
    (``any_valid`` False), capability per dollar once one exists."""
    util = util / jnp.maximum(jnp.sum(util), 1e-9)
    w = jnp.sum(norm_scores * util, axis=-1)
    w = jnp.where(jnp.logical_and(per_dollar, any_valid), w / prices, w)
    w = w / jnp.maximum(jnp.mean(w), 1e-9)
    return 1.0 + strength * (w - 1.0)


def ranking_losses(f: jnp.ndarray, y: jnp.ndarray,
                   mask: jnp.ndarray) -> jnp.ndarray:
    """RGPE ranking loss of each model and sample: the count of
    ordered pairs of valid observations (j, k) with
    ``(f_j < f_k) xor (y_j < y_k)``. ``f`` (K, S, R) samples at the R
    padded observations, ``y`` (R,), ``mask`` (R,); returns (K, S)
    int32. The compare, the xor and the count fuse into one reduction:
    the (K, S, R, R) pair tensor is never needed whole."""
    pmask = mask[:, None] & mask[None, :]
    y_less = y[:, None] < y[None, :]
    wrong = ((f[..., :, None] < f[..., None, :]) != y_less) & pmask
    return jnp.sum(wrong, axis=(-2, -1), dtype=jnp.int32)


def _order_statistics(losses: jnp.ndarray, ks, vmax: int):
    """The k-th smallest (0-based) of each row of integer ``losses``
    (K, S) with values in [0, vmax], for each static k in ``ks``:
    a_(k) = #{v < vmax : #{s : l_s <= v} <= k}. Counting in place of
    a sort: on a TPU v5e, ``jnp.sort`` of these int32 rows took 76 ms
    of each 4,096-lane Karasu matrix's 0.98 s scan (PERF.md section
    6); the counts fuse with the compares around them."""
    v = jnp.arange(vmax, dtype=losses.dtype)
    cnt = jnp.sum(losses[:, :, None] <= v, axis=1)  # (K, vmax)
    return [jnp.sum(cnt <= k, axis=-1) for k in ks]


def _diluted(losses: jnp.ndarray, vmax: int) -> jnp.ndarray:
    """Per row (K,): is its median loss above the 95th percentile of
    row 0's? Both are linear interpolations between order statistics
    at the exact positions (S - 1) / 2 and 95 (S - 1) / 100, compared
    in integers scaled by 200, so no rounding can tip a tie."""
    s = losses.shape[-1]
    lo, frac = divmod(95 * (s - 1), 100)
    hi = min(lo + 1, s - 1)
    a_m0, a_m1, a_lo, a_hi = _order_statistics(
        losses, [(s - 1) // 2, s // 2, lo, hi], vmax)
    p95_x200 = 2 * (100 * a_lo[0] + frac * (a_hi[0] - a_lo[0]))
    return 100 * (a_m0 + a_m1) > p95_x200


def rgpe_weights(losses: jnp.ndarray, eligible: jnp.ndarray,
                 vmax: int) -> jnp.ndarray:
    """RGPE weights (K,) from ranking losses (K, S), row 0 the
    target's: w_i is the share of samples in which model i has the
    lowest loss among the eligible models, a tie split equally.

    ``eligible`` (K,) masks empty support slots (the target is always
    eligible). The paper's guard against weight dilution drops a
    support model whose median loss exceeds the 95th percentile of
    the target's loss before the shares are counted, so the weights
    still sum to 1. Each share is counted as the integer
    lcm(1..K) / ties and the sum divided once: exact, whatever the
    order of the reduction, so every backend gives the same bits; a
    model with every sample gets exactly 1."""
    k, s = losses.shape
    lcm = math.lcm(*range(1, k + 1))
    if lcm >= 2 ** 31 or s * lcm >= 2 ** 53:
        raise ValueError(f"{k} models x {s} samples overflow the exact "
                         "count of RGPE shares")
    keep = eligible & (~_diluted(losses, vmax) | (jnp.arange(k) == 0))
    big = jnp.iinfo(losses.dtype).max
    masked = jnp.where(keep[:, None], losses, big)
    tied = masked == jnp.min(masked, axis=0)
    ties = jnp.sum(tied, axis=0)
    total = jnp.sum(jnp.where(tied, lcm // ties, 0).astype(jnp.float64),
                    axis=1)
    return jnp.where(total == s * lcm, 1.0, total / float(s * lcm))


def ensemble_predict(w: jnp.ndarray, mu: jnp.ndarray, sigma: jnp.ndarray,
                     y_mean, y_std, mean_s: jnp.ndarray,
                     var_s: jnp.ndarray):
    """The RGPE predictive at the candidates in the target's units:
    mu = sum_i w_i mu_i and sigma^2 = sum_i w_i^2 sigma_i^2, with the
    target's own (``mu``, ``sigma``) weighted by ``w[0]`` and the
    support models' standardized means ``mean_s`` (M, C) and
    variances ``var_s`` (M, C) mapped through the target's
    standardization. Where the target holds all the weight the result
    is the target's posterior, bit for bit."""
    w_t, w_s = w[0], w[1:]
    mix_mu = (w_t * mu + y_std * jnp.sum(w_s[:, None] * mean_s, axis=0)
              + y_mean * jnp.sum(w_s))
    mix_var = ((w_t * sigma) ** 2
               + y_std ** 2 * jnp.sum((w_s * w_s)[:, None] * var_s,
                                      axis=0))
    alone = w_t == 1.0
    return (jnp.where(alone, mu, mix_mu),
            jnp.where(alone, sigma, jnp.sqrt(mix_var)))
