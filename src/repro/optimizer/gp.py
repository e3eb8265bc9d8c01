"""Batched RBF Gaussian process as pure jnp ops (masked + padded).

Mirrors ``repro.tuning.gp.GP`` (the scipy reference the parity tests
pin against) op for op: per-dimension median-heuristic length scales,
y standardization, noise jitter, exact Cholesky inference. Observation
sets are carried padded to a fixed slot count (``common.bucketing.
next_pow2`` of the run budget) with a validity mask, so one compiled
program serves every lane at every BO round; callers ``jax.vmap`` these
functions over a leading lane axis.

Masking convention: padded observation rows contribute an identity
block to the kernel matrix (diagonal 1 + noise, zero cross terms) and a
zero target, so their Cholesky/solve contributions vanish exactly —
fit/predict on a masked set equals fit/predict on the dense subset.

Every contraction is written as a broadcast multiply and a ``jnp.sum``
over the contracted axis, never as ``@``/``dot`` or a triangular solve:
the TPU has no float64 matmul unit, and its compiler emulates each
float64 dot or solve as a ``while`` loop of bfloat16 passes over split
operands. The contractions here are 4 to 16 long, so those passes
would run on tiles that are nearly all padding; as multiply-and-sum
they fuse into the surrounding elementwise code, and the scanned BO
round of ``optimizer.replay`` compiles to a loop-free body. On the CPU a
contraction this short gains nothing from a BLAS call either.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax.numpy as jnp


class GPState(NamedTuple):
    """Posterior state of one fitted lane (a pytree; vmap-friendly)."""

    l_inv: jnp.ndarray  # (P, P) inverse of the lower Cholesky factor
    #                       of K + noise*I (lower triangular)
    alpha: jnp.ndarray  # (P,) K^-1 y_standardized
    x: jnp.ndarray  # (P, D) padded observations
    mask: jnp.ndarray  # (P,) observation validity
    scales: jnp.ndarray  # (D,) median-heuristic length scales
    y_mean: jnp.ndarray  # ()
    y_std: jnp.ndarray  # ()


def median_scales(x: jnp.ndarray, mask: jnp.ndarray, m: jnp.ndarray,
                  rows: Optional[int] = None) -> jnp.ndarray:
    """Per-dimension median of |x_i - x_j| over all valid pairs
    (self-pairs included, as in the reference), floored at 1.0 for
    near-constant dimensions.

    The |x_i - x_j| matrix is symmetric with a zero diagonal, so the
    m^2-multiset's order statistics are recovered from the unique
    pairs alone: the m smallest entries are the diagonal zeros (every
    pair distance is >= 0), and the k-th smallest for k >= m is the
    (k - m)//2-th smallest pair value (each pair appears twice). Only
    the r(r-1)/2 upper-triangle pairs are built — pass ``rows`` when
    valid observations are known to live in a prefix of the padded
    slots (the replay engine's run budget). Invalid pairs sort to the
    back as +inf; the sort runs along the last (pair) axis, which XLA's
    CPU backend handles markedly faster than leading-axis sorts."""
    r = x.shape[0] if rows is None else rows
    iu, ju = np.triu_indices(r, 1)
    u = jnp.abs(x[iu] - x[ju])  # (T, D)
    pair_ok = mask[iu] & mask[ju]
    u = jnp.where(pair_ok[:, None], u, jnp.inf).T  # (D, T)
    u = jnp.sort(u, axis=-1)

    def stat(k):  # k-th smallest of the m*m masked-median multiset
        return jnp.where(k < m, 0.0,
                         u[:, jnp.maximum((k - m) // 2, 0)])

    med = 0.5 * (stat((m * m - 1) // 2) + stat((m * m) // 2))
    return jnp.where(med > 1e-9, med, 1.0)


def _kernel(a: jnp.ndarray, b: jnp.ndarray,
            scales: jnp.ndarray) -> jnp.ndarray:
    """RBF kernel via the expansion |a'|^2 + |b'|^2 - 2 a'.b' of the
    scaled squared distance (clipped at 0 so self-distances stay
    exactly zero under rounding); a'.b' as multiply-and-sum (module
    docstring)."""
    a = a / scales
    b = b / scales
    na = jnp.sum(a * a, axis=-1)
    nb = jnp.sum(b * b, axis=-1)
    ab = jnp.sum(a[:, None, :] * b[None, :, :], axis=-1)
    sq = jnp.maximum(na[:, None] + nb[None, :] - 2.0 * ab, 0.0)
    return jnp.exp(-0.5 * sq)


def cholesky(k: jnp.ndarray) -> jnp.ndarray:
    """Lower Cholesky factor of one small SPD matrix, column by column.

    Used in place of ``jnp.linalg.cholesky``, which the TPU compiler
    refuses in float64 inside any partitioned program (``shard_map`` or
    automatic sharding: "A tuple parameter that is being flattened
    shouldn't have frontend attributes") — the sharded replay is one.
    ``k`` is P x P with P the pow2 observation-slot count, so the
    unrolled loop stays short. The column update is a multiply and sum,
    not a matvec: a float64 matvec would compile to a multi-pass loop
    on the TPU (module docstring)."""
    p = k.shape[-1]
    chol = jnp.zeros_like(k)
    for j in range(p):
        d = jnp.sqrt(k[j, j] - jnp.sum(chol[j, :j] ** 2))
        col = (k[j + 1:, j]
               - jnp.sum(chol[j + 1:, :j] * chol[j, :j], axis=-1)) / d
        chol = chol.at[j, j].set(d).at[j + 1:, j].set(col)
    return chol


def tril_inverse(chol: jnp.ndarray) -> jnp.ndarray:
    """Inverse of a lower-triangular factor, row by row by forward
    substitution: ``row_j = (e_j - sum_{i<j} L[j,i] inv[i,:]) / L[j,j]``.

    Unrolled over the P slots like :func:`cholesky`, in place of a
    triangular solve (module docstring). A masked slot's identity
    block in ``chol`` stays an identity block (scaled by its diagonal)
    in the inverse."""
    p = chol.shape[-1]
    eye = jnp.eye(p, dtype=chol.dtype)
    inv = jnp.zeros_like(chol)
    for j in range(p):
        acc = jnp.sum(chol[j, :j, None] * inv[:j], axis=0)
        inv = inv.at[j].set((eye[j] - acc) / chol[j, j])
    return inv


def gp_fit(x: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray,
           noise: float = 1e-3,
           median_rows: Optional[int] = None) -> GPState:
    """Fit one lane's GP on its masked observation set.

    ``x`` (P, D), ``y`` (P,), ``mask`` (P,) — padded rows are ignored
    exactly (see module docstring). Constant-y sets fall back to unit
    std (the reference's degenerate-input guard). ``median_rows``
    bounds the slots the length-scale median looks at (see
    :func:`median_scales`)."""
    m = jnp.sum(mask)
    y_mean = jnp.sum(jnp.where(mask, y, 0.0)) / m
    var = jnp.sum(jnp.where(mask, (y - y_mean) ** 2, 0.0)) / m
    y_std = jnp.sqrt(var)
    y_std = jnp.where(
        y_std <= 1e-12 * jnp.maximum(1.0, jnp.abs(y_mean)), 1.0, y_std)
    yn = jnp.where(mask, (y - y_mean) / y_std, 0.0)
    scales = median_scales(x, mask, m, rows=median_rows)
    pmask = mask[:, None] & mask[None, :]
    k = jnp.where(pmask, _kernel(x, x, scales), 0.0)
    k = k + jnp.diag(jnp.where(mask, noise, 1.0 + noise))
    l_inv = tril_inverse(cholesky(k))
    # alpha = L^-T (L^-1 yn)
    alpha = jnp.sum(l_inv * jnp.sum(l_inv * yn, axis=-1)[:, None], axis=0)
    return GPState(l_inv=l_inv, alpha=alpha, x=x, mask=mask,
                   scales=scales, y_mean=y_mean, y_std=y_std)


def gp_predict(state: GPState, xs: jnp.ndarray):
    """Posterior (mu, sigma) at candidate points ``xs`` (C, D).

    The predictive variance 1 - k* K^-1 k*^T is computed as
    1 - ||L^-1 k*^T||^2 with the L^-1 that :func:`gp_fit` carries in
    the state, so the per-candidate work is two multiply-and-sums, no
    solve (module docstring). Equal to the reference's cho_solve form
    up to rounding; the selection grid in the replay engine absorbs
    the ulp difference."""
    ks = _kernel(xs, state.x, state.scales) * state.mask[None, :]
    mu = jnp.sum(ks * state.alpha, axis=-1)
    w = jnp.sum(state.l_inv[:, None, :] * ks[None, :, :], axis=-1)
    var = jnp.clip(1.0 - jnp.sum(w * w, axis=0), 1e-9, None)
    return (mu * state.y_std + state.y_mean,
            jnp.sqrt(var) * state.y_std)


def gp_fit_predict(x, y, mask, xs, noise: float = 1e-3):
    """Convenience fused fit+predict (one lane); vmap for batches."""
    return gp_predict(gp_fit(x, y, mask, noise), xs)


def gp_joint_posterior(state: GPState, xs: jnp.ndarray):
    """Joint posterior of the latent function at ``xs`` (C, D), in the
    fit's standardized units: the mean (C,) and the full covariance
    (C, C) = K(xs, xs) - W^T W with W = L^-1 k*^T. This is what a
    Karasu support model carries (``optimizer.scenarios.
    support_history``); its diagonal is :func:`gp_predict`'s variance
    before scaling."""
    ks = _kernel(xs, state.x, state.scales) * state.mask[None, :]
    mean = jnp.sum(ks * state.alpha, axis=-1)
    w = jnp.sum(state.l_inv[:, None, :] * ks[None, :, :], axis=-1)
    cov = (_kernel(xs, xs, state.scales)
           - jnp.sum(w[:, :, None] * w[:, None, :], axis=0))
    return mean, cov


def loo_posterior(state: GPState, y: jnp.ndarray):
    """Leave-one-out predictive (mean, sd) at each observation, in
    standardized units, in closed form from K^-1 (Rasmussen and
    Williams, eq. 5.12): mu_-j = yn_j - [K^-1 yn]_j / [K^-1]_jj and
    var_-j = 1 / [K^-1]_jj, with K^-1 = L^-T L^-1 so that
    [K^-1]_jj is the squared norm of column j of L^-1. ``y`` is the
    padded observation vector the state was fitted on; masked slots
    give values no caller reads."""
    yn = jnp.where(state.mask, (y - state.y_mean) / state.y_std, 0.0)
    kinv_diag = jnp.sum(state.l_inv * state.l_inv, axis=0)
    return yn - state.alpha / kinv_diag, jnp.sqrt(1.0 / kinv_diag)


def joint_samples(mean: jnp.ndarray, cov: jnp.ndarray, mask: jnp.ndarray,
                  z: jnp.ndarray, jitter: float) -> jnp.ndarray:
    """Joint samples ``mean + L z`` (S, R) of one model at R padded
    points from standard normals ``z`` (S, R), L the Cholesky factor
    of ``cov`` + ``jitter`` I over the valid points (masked points get
    an identity block, so the valid prefix's samples depend on the
    valid prefix of ``z`` alone)."""
    pmask = mask[:, None] & mask[None, :]
    k = jnp.where(pmask, cov, 0.0) + jnp.diag(
        jnp.where(mask, jitter, 1.0))
    chol = cholesky(k)
    return mean + jnp.sum(chol[None, :, :] * z[:, None, :], axis=-1)
