"""Sharded fleet scoring: one compiled dispatch across all devices.

A fleet re-fingerprinting round is a stack of *independent* per-node
scoring requests (paper §III-C scores each execution only against the
predecessors of its own (node x benchmark type) chain, so request
graphs never cross shard boundaries). ``ShardedScorer`` therefore
partitions the stacked request batch (leading axis R) across a 1-D
``"fleet"`` device mesh with ``jax.experimental.shard_map`` and runs
the *same* pure scoring function as ``serving.FingerprintEngine``
(``make_score_fn``) vmapped over each device's local requests — one
jit-compiled, donation-enabled dispatch per flush, scaling with device
count.

Verifiable on CPU: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and compare
against a single-device scorer — the partitioning is along the request
axis only, so the sharded scores are bit-identical
(``tests/test_fleet.py``).
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, Optional, Sequence

import numpy as np

from repro.common.mesh import (axis_specs, build_mesh, shard_map_1d,
                               shard_size)
from repro.core.model import PeronaModel
from repro.core.preprocess import Preprocessor
from repro.obs.jaxstat import JitSite, instance_site
from repro.serving.engine import ARG_NAMES, make_score_fn


#: Parameter sets kept placed on the mesh: the incumbent and one
#: candidate (a warm or canary dispatch must not evict the incumbent).
PLACED_PARAMS = 2


class ScorerCompileError(RuntimeError):
    """The scoring program failed to trace, lower or compile. That is a
    fault of the program, not of the device: every retry would fail the
    same way, so callers must not retry it."""


class ShardedScorer:
    """shard_map(vmap(score_fn)) over a 1-D device mesh."""

    def __init__(self, model: PeronaModel, preproc: Preprocessor,
                 devices: Optional[Sequence] = None):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        self.mesh = build_mesh("fleet", devices)
        self.n_devices = self.mesh.devices.size
        # per-instance jit accounting on the obs registry
        self.jit = JitSite(instance_site("fleet.scorer"))

        fn = make_score_fn(model, preproc, on_trace=self.jit.tick)
        vmapped = jax.vmap(fn, in_axes=(None,) + (0,) * len(ARG_NAMES))
        sharded = shard_map_1d(
            vmapped, self.mesh,
            in_specs=axis_specs("fleet", len(ARG_NAMES), n_const=1),
            out_specs=P("fleet"))
        # stacked request buffers are rebuilt per flush: donate them
        self.donate_argnums = tuple(range(1, 1 + len(ARG_NAMES)))
        self._call = jax.jit(sharded,
                             donate_argnums=self.donate_argnums)
        # inputs go straight to their mesh placement: request rows
        # split over the devices, params replicated on every device
        self._rows = NamedSharding(self.mesh, P("fleet"))
        self._replicated = NamedSharding(self.mesh, P())
        self._programs: Dict[tuple, object] = {}
        # id(params) -> (params, placed), least recently used first
        self._placed: collections.OrderedDict = collections.OrderedDict()
        self._placed_lock = threading.Lock()

    @property
    def trace_count(self) -> int:
        """jit tracings so far (1 per distinct (R, bucket) shape)."""
        return self.jit.count

    def pad_requests(self, n_requests: int) -> int:
        """Power-of-two request-axis size, divisible by the mesh."""
        return shard_size(n_requests, self.n_devices)

    def place_params(self, params):
        """``params`` replicated over the mesh, placed once per params
        object. The last :data:`PLACED_PARAMS` objects stay placed, so
        flushes with the incumbent and warm or canary dispatches with a
        candidate, from different threads, never evict each other."""
        import jax

        key = id(params)
        with self._placed_lock:
            hit = self._placed.get(key)
            if hit is not None and hit[0] is params:
                self._placed.move_to_end(key)
                return hit[1]
        placed = jax.device_put(params, self._replicated)
        with self._placed_lock:
            self._placed[key] = (params, placed)
            self._placed.move_to_end(key)
            while len(self._placed) > PLACED_PARAMS:
                self._placed.popitem(last=False)
        return placed

    def place_stack(self, stack: Dict[str, np.ndarray]) -> tuple:
        """The request arrays of ``stack``, in ``ARG_NAMES`` order,
        split by request over the mesh."""
        import jax

        return tuple(jax.device_put(stack[k], self._rows)
                     for k in ARG_NAMES)

    def _program(self, params, args):
        """The compiled program for this input signature: traced,
        lowered and compiled on first use, where any failure raises
        :class:`ScorerCompileError`."""
        import jax

        sig = tuple((a.shape, a.dtype)
                    for a in jax.tree_util.tree_leaves((params, args)))
        exe = self._programs.get(sig)
        if exe is None:
            try:
                exe = self._call.lower(params, *args).compile()
            except Exception as e:  # noqa: BLE001 — re-raised, typed
                raise ScorerCompileError(
                    f"scoring program for {args[0].shape[:2]} "
                    f"(requests, rows) failed to compile: "
                    f"{type(e).__name__}: {e}") from e
            self._programs[sig] = exe
        return exe

    def score_stack(self, params, stack: Dict[str, np.ndarray]
                    ) -> Dict[str, np.ndarray]:
        """Score a stacked request batch: every array in ``stack`` has
        leading axis R (a multiple of the device count; see
        :meth:`pad_requests`) then the per-request padded row bucket.
        Returns numpy outputs with the same leading axes."""
        from repro.serving.engine import silence_unusable_donation

        r = stack[ARG_NAMES[0]].shape[0]
        if r % self.n_devices:
            raise ValueError(
                f"request axis {r} not divisible by the "
                f"{self.n_devices}-device fleet mesh; pad with "
                "pad_requests() first")
        params = self.place_params(params)
        args = self.place_stack(stack)
        with silence_unusable_donation(), \
                self.jit.dispatch(
                    "fleet.score_stack",
                    args={"requests": r,
                          "bucket": stack[ARG_NAMES[0]].shape[1]}):
            out = self._program(params, args)(params, *args)
        return {k: np.asarray(v) for k, v in out.items()}
