"""Public wrapper for edge_softmax: pads N to a block multiple and
moves the node axis to the lanes for the kernel (``kernel.py``).

The custom VJP saves the forward's attention weights as residuals, so
the backward pass is three einsums over (g, att, q, k, v) — the softmax
is never recomputed and the reference forward is never re-run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.common.bucketing import next_pow2
from repro.kernels.edge_softmax import kernel as K

BLOCK_N = 512


def _interpret_default() -> bool:
    """Compiled on a TPU backend, interpreted everywhere else."""
    return jax.default_backend() != "tpu"


def _block_for(n: int) -> int:
    """Node-axis block: smallest power of two >= n, in [128, BLOCK_N]."""
    return min(BLOCK_N, next_pow2(n, 128))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _agg(q, k, v, mask, scale, interpret):
    N = q.shape[0]
    if N == 0:  # empty graph: nothing to launch
        att_shape = (0,) + q.shape[1:-1] + mask.shape[1:]
        return jnp.zeros_like(q), jnp.zeros(att_shape, jnp.float32)
    blk = _block_for(N)
    pad = (-N) % blk
    if pad:
        padw = lambda a: [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        q = jnp.pad(q, padw(q))
        k = jnp.pad(k, padw(k))
        v = jnp.pad(v, padw(v))
        mask = jnp.pad(mask, padw(mask))
    out, att = K.edge_softmax_aggregate(
        jnp.transpose(q, (1, 2, 0)), jnp.transpose(k, (1, 2, 3, 0)),
        jnp.transpose(v, (1, 2, 3, 0)), mask.T, scale=scale,
        block_n=blk, interpret=interpret)
    return (jnp.transpose(out, (2, 0, 1))[:N],
            jnp.transpose(att, (2, 1, 0))[:N])


def _fwd(q, k, v, mask, scale, interpret):
    out, att = _agg(q, k, v, mask, scale, interpret)
    return (out, att), (q, k, v, att)


def _bwd(scale, interpret, res, g):
    q, k, v, att = res
    g_out, g_att = g
    gf = g_out.astype(jnp.float32)
    # d(att): from the aggregate output plus any direct att cotangent
    da = jnp.einsum("nhf,nphf->nhp", gf, v.astype(jnp.float32))
    da = da + g_att.astype(jnp.float32)
    # softmax VJP; att is 0 on masked / fully-masked slots, so ds is too
    ds = att * (da - jnp.sum(att * da, axis=-1, keepdims=True))
    dq = scale * jnp.einsum("nhp,nphf->nhf", ds, k.astype(jnp.float32))
    dk = scale * jnp.einsum("nhp,nhf->nphf", ds, q.astype(jnp.float32))
    dv = jnp.einsum("nhp,nhf->nphf", att, gf)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None)


_agg.defvjp(_fwd, _bwd)


def edge_softmax_aggregate(q, k, v, mask, scale=None,
                           interpret: bool | None = None):
    """Single-head: q (N, F); k/v (N, P, F) -> (out (N, F), att (N, P)).
    Multi-head: q (N, H, hd); k/v (N, P, H, hd) -> (out (N, H, hd),
    att (N, H, P)). mask: (N, P), shared across heads.
    """
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    interpret = _interpret_default() if interpret is None else interpret
    single = q.ndim == 2
    if single:
        q, k, v = q[:, None, :], k[:, :, None, :], v[:, :, None, :]
    out, att = _agg(q, k, v, mask.astype(bool), scale, interpret)
    if single:
        return out[:, 0, :], att[:, 0, :]
    return out, att
