"""Public wrapper for the RG-LRU scan kernel (grad via oracle VJP)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rg_lru import kernel as K
from repro.kernels.rg_lru import ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _scan(a, b, h0, interpret):
    y, h_last = K.rg_lru_scan(a, b, h0, interpret=interpret)
    return y, h_last


def _scan_fwd(a, b, h0, interpret):
    return _scan(a, b, h0, interpret), (a, b, h0)


def _scan_bwd(interpret, res, g):
    a, b, h0 = res
    _, vjp = jax.vjp(lambda a_, b_, h_: ref.linear_scan(a_, b_, h_),
                     a, b, h0)
    return vjp(g)


_scan.defvjp(_scan_fwd, _scan_bwd)


def linear_scan(a, b, h0=None, interpret: bool | None = None):
    """a, b: (B, S, C); h0 optional (B, C). Returns (y, h_last)."""
    interpret = _interpret_default() if interpret is None else interpret
    if h0 is None:
        h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    return _scan(a.astype(jnp.float32), b.astype(jnp.float32),
                 h0.astype(jnp.float32), interpret)
