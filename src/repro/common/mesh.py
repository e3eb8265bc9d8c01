"""1-D device-mesh plumbing shared by every shard_map consumer.

Extracted from ``fleet.shard.ShardedScorer`` so the fleet scorer, the
fleet service's request stacking and the optimizer's sharded replay
engine share one policy:

- :func:`pow2_devices` — the largest power-of-two prefix of a device
  list (a pow2 mesh keeps pow2-padded batch axes evenly divisible);
- :func:`build_mesh` — a 1-D ``jax.sharding.Mesh`` over that prefix;
- :func:`shard_size` — the padded batch-axis length for a mesh: the
  smallest power of two that is >= the row count, >= ``floor`` and
  divisible by the device count;
- :func:`pad_lanes` / :func:`stack_padded` — build
  the padded (donatable) batch buffers;
- :func:`axis_specs` / :func:`shard_map_1d` — ``jax.shard_map``
  wrapping with leading-axis partition specs.

Every consumer partitions along an *independent-rows* axis only
(scoring requests, BO lanes), so sharded outputs are bit-identical to
their single-device counterparts — asserted under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` by
``tests/test_fleet.py`` and ``tests/test_optimizer.py``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.common.bucketing import next_pow2


def pow2_devices(devices: Optional[Sequence] = None) -> List:
    """Largest power-of-two prefix of ``devices`` (default: all local
    devices)."""
    if devices is None:
        import jax

        devices = jax.devices()
    devices = list(devices)
    n = 1
    while n * 2 <= len(devices):
        n *= 2
    return devices[:n]


def build_mesh(axis: str, devices: Optional[Sequence] = None):
    """1-D mesh named ``axis`` over the pow2 prefix of ``devices``."""
    from jax.sharding import Mesh

    return Mesh(np.asarray(pow2_devices(devices)), (axis,))


def shard_size(n: int, n_devices: int = 1, floor: int = 1) -> int:
    """Padded batch-axis length: smallest power of two >= ``n`` that is
    also >= ``floor`` and divisible by the (pow2) device count."""
    return next_pow2(n, max(floor, n_devices, 1))


def pad_lanes(a: np.ndarray, size: int) -> np.ndarray:
    """Pad axis 0 to ``size`` rows by repeating row 0 — for batch axes
    whose padding must stay numerically well-formed (e.g. GP lane
    tables, where zero rows would produce degenerate kernels). Padded
    rows are masked out / sliced off by the caller."""
    if len(a) == size:
        return a
    reps = np.repeat(a[:1], size - len(a), axis=0)
    return np.concatenate([a, reps], axis=0)


def stack_padded(inputs: Sequence[Dict[str, np.ndarray]],
                 size: int) -> Dict[str, np.ndarray]:
    """Stack per-request input dicts along a new leading axis of
    ``size`` rows (zero rows past ``len(inputs)``) — the donatable
    stacked buffer a sharded dispatch consumes."""
    first = inputs[0]
    out = {k: np.zeros((size,) + v.shape, v.dtype)
           for k, v in first.items()}
    for r, d in enumerate(inputs):
        for k, v in d.items():
            out[k][r] = v
    return out


def axis_specs(axis: str, n_batched: int, n_const: int = 0):
    """``n_const`` replicated specs followed by ``n_batched``
    leading-axis-partitioned specs."""
    from jax.sharding import PartitionSpec as P

    return (P(),) * n_const + (P(axis),) * n_batched


def shard_map_1d(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: the
    batched buffers are donated and never replicated."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
