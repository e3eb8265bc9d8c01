"""Fused edge-softmax aggregation Pallas kernel (Perona GNN).

Lane-major layout: the node axis N is the minor (lane) axis of every
operand — q (H, hd, N), k/v (P, H, hd, N), mask (P, N), out (H, hd, N),
att (P, H, N) — and the grid tiles N in blocks of ``bn`` (a multiple of
128). Every block's last two dimensions are then (full, bn), which the
TPU's (8, 128) tiling accepts for any H, hd and P; Perona's own widths
(hd = 8 of K = 32, H = 4, P = 3) would otherwise sit in padded tiles.
One grid step handles all heads. Block VMEM at bn = 512, H = 4,
hd = 8, P = 3 (f32): q and out 64 KB each, k and v 192 KB each, att
and mask 48 + 16 KB (H = 4 and P = 3 pad to 8 sublanes) — ~0.6 MB,
~1.2 MB double-buffered. The score reduction over hd (sublanes), the
masked softmax over P (unrolled) and the weighted combine are fused in
one VMEM round trip (VPU work; no MXU needed at these widths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, att_ref, *, scale: float):
    n_pred = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32)  # (H, hd, bn)
    valid = [mask_ref[p:p + 1, :] != 0 for p in range(n_pred)]  # (1, bn)
    s = [jnp.where(valid[p],
                   jnp.sum(q * k_ref[p].astype(jnp.float32), axis=1)
                   * scale, NEG_INF)
         for p in range(n_pred)]  # (H, bn) each
    m = functools.reduce(jnp.maximum, s)
    e = [jnp.exp(s[p] - m) * valid[p].astype(jnp.float32)
         for p in range(n_pred)]
    denom = jnp.maximum(functools.reduce(jnp.add, e), 1e-30)
    out = jnp.zeros(q.shape, jnp.float32)
    for p in range(n_pred):
        att = e[p] / denom
        att_ref[p] = att.astype(att_ref.dtype)
        out = out + att[:, None, :] * v_ref[p].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def edge_softmax_aggregate(q, k, v, mask, *, scale: float,
                           block_n: int = 512, interpret: bool = False):
    """Lane-major operands: q (H, hd, N); k/v (P, H, hd, N); mask
    (P, N) (bool or int).

    Returns (out (H, hd, N), att (P, H, N)). The mask is shared across
    heads; each node block is one grid step.
    """
    P, H, hd, N = k.shape
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    kernel = functools.partial(_kernel, scale=scale)
    out, att = pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((H, hd, bn), lambda i: (0, 0, i)),
            pl.BlockSpec((P, H, hd, bn), lambda i: (0, 0, 0, i)),
            pl.BlockSpec((P, H, hd, bn), lambda i: (0, 0, 0, i)),
            pl.BlockSpec((P, bn), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((H, hd, bn), lambda i: (0, 0, i)),
            pl.BlockSpec((P, H, bn), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, hd, N), q.dtype),
            jax.ShapeDtypeStruct((P, H, N), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask.astype(jnp.int32))
    return out, att
