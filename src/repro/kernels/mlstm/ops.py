"""Public wrapper for the chunkwise mLSTM kernel.

Model layout (B, S, H, hd) + gates (B, S, H) is reshaped to the kernel's
(B*H, S, hd). Gradients fall back to the oracle VJP (a fused backward
kernel is TPU follow-up work). Fresh-state calls only — the model passes
state=None during training; carried state is supported via the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mlstm import kernel as K
from repro.kernels.mlstm import ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _mlstm(q, k, v, log_i, log_f, chunk, interpret):
    return K.mlstm_chunkwise(q, k, v, log_i, log_f, chunk=chunk,
                             interpret=interpret)


def _fwd(q, k, v, log_i, log_f, chunk, interpret):
    out = _mlstm(q, k, v, log_i, log_f, chunk, interpret)
    return out, (q, k, v, log_i, log_f)


def _bwd(chunk, interpret, res, g):
    q, k, v, log_i, log_f = res
    _, vjp = jax.vjp(
        lambda *a: ref.mlstm_chunkwise(*a, chunk=chunk), q, k, v, log_i,
        log_f)
    return vjp(g)


_mlstm.defvjp(_fwd, _bwd)


def mlstm_chunkwise(q, k, v, log_i, log_f, *, chunk: int = 64, state=None,
                    interpret: bool | None = None):
    """Model-layout entry: q/k/v (B,S,H,hd); gates (B,S,H).

    Returns (h (B,S,H,hd), state (C (B,H,hd,hd), n (B,H,hd), m (B,H))).
    """
    if state is not None:
        # carried state (prefill continuation): oracle path
        B, S, H, hd = q.shape
        tr = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * H, S, -1)
        trg = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * H, S)
        Cs, ns, ms = state
        st = (Cs.reshape(B * H, hd, hd), ns.reshape(B * H, hd),
              ms.reshape(B * H))
        h, (C, n, m) = ref.mlstm_chunkwise(
            tr(q), tr(k), tr(v), trg(log_i), trg(log_f), chunk=chunk,
            state=st)
        h = jnp.moveaxis(h.reshape(B, H, S, hd), 1, 2)
        return h, (C.reshape(B, H, hd, hd), n.reshape(B, H, hd),
                   m.reshape(B, H))
    interpret = _interpret_default() if interpret is None else interpret
    B, S, H, hd = q.shape
    tr = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * H, S, -1)
    trg = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * H, S)
    h, (C, n, m) = _mlstm(tr(q), tr(k), tr(v),
                          trg(log_i.astype(jnp.float32)),
                          trg(log_f.astype(jnp.float32)), chunk, interpret)
    h = jnp.moveaxis(h.reshape(B, H, S, hd), 1, 2)
    return h, (C.reshape(B, H, hd, hd), n.reshape(B, H, hd),
               m.reshape(B, H))
