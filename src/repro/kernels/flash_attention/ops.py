"""Public wrapper for the flash attention kernel.

Model-facing layout is (B, S, H, D) (matching repro.models.attention);
the kernel uses (B, H, S, D). Training gradients use a custom_vjp whose
backward recomputes with the reference (flash-backward kernels are a TPU
follow-up; the forward kernel is the inference hot path).

On non-TPU backends the kernel runs in interpret mode (or pass
interpret=True), which is how this repo validates it on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, scale, interpret):
    return K.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 scale=scale, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, scale, interpret):
    out = _flash(q, k, v, causal, window, scale, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention(q_, k_, v_, causal=causal,
                                         window=window, scale=scale),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, interpret: bool | None = None):
    """q: (B, S, H, D); k/v: (B, T, KH, D). Returns (B, S, H, D)."""
    interpret = _interpret_default() if interpret is None else interpret
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, window, scale, interpret)
    return jnp.swapaxes(out, 1, 2)
