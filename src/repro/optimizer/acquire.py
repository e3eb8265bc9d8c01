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
"""

from __future__ import annotations

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
