"""Counter-based RNG streams: order-independent, placement-independent
draws shared by the host simulators and the device replay program.

Every stochastic quantity of the scenario stack is a *pure function* of
a fold-in chain over ``jax.random``'s counter-based threefry generator:

    value = f(fold_in(fold_in(root(seed), stream_tag), id0, id1, ...))

No hidden sequential stream state means no call-order dependence: the
draw a (workload, configuration) cell gets is the same whether it is
queried first or last, from the host reference tuner or from inside the
compiled replay program, eagerly or under ``jit``/``vmap``/``shard_map``
(threefry is deterministic across those execution contexts; asserted by
tests/test_seeded_rng.py).

The host-side fingerprint simulators are numpy-based; for them
:func:`folded_generator` derives an independent ``np.random.Generator``
from a hashable path (ints and strings), so per-group draws are a pure
function of ``(seed, round, benchmark_type, machine_type)`` rather than
a position in one shared stream.

The float64 draws run under :func:`x64_streams`, which also pins
threefry's original (non-partitionable) bit derivation: JAX 0.5 made
the partitionable derivation the default, and the seeded realizations
the tests and recorded results are defined on would otherwise change
with the installed JAX.
"""

from __future__ import annotations

import contextlib
import hashlib
from typing import Tuple, Union

import numpy as np

# fold_in stream tags: one per stochastic quantity, so streams never
# collide even for equal entity ids
# stream tags pick the realization; values are arbitrary but fixed —
# bumping one re-rolls every draw downstream of that stream
STREAM_WORKLOAD_PARAMS = 31  # scout workload latent demand vectors
STREAM_CONTENTION = 32  # scout per-(workload, config) contention noise
STREAM_ARRIVALS = 33  # fleet telemetry arrival-process jitter
STREAM_FAULTS = 34  # fleet fault-injection decisions (fleet.faults)
STREAM_RETRY = 35  # scorer retry-backoff jitter (fleet.service)
STREAM_SUPPORT = 36  # Karasu support history: its search seeds and
#                      each lane's choice of support searches
STREAM_RGPE = 37  # RGPE posterior samples (optimizer.replay, tuning.karasu)


def root_key(seed: int):
    """The raw threefry root key for a dataset seed."""
    import jax

    return jax.random.PRNGKey(seed)


def stream_key(seed: int, stream_tag: int):
    """``fold_in(root(seed), stream_tag)`` as a host uint32 array —
    the per-quantity key shipped to device programs."""
    import jax

    return np.asarray(jax.random.fold_in(root_key(seed), stream_tag))


@contextlib.contextmanager
def x64_streams():
    """Float64 on the pinned threefry derivation (see module doc).
    Wrap every call that traces or lowers a draw of these streams —
    the flag is read when ``random_bits`` lowers, not when it traces."""
    import jax

    with jax.enable_x64(), jax.threefry_partitionable(False):
        yield


# --------------------------------------------------------------- device
def lognormal_noise_row(key_stream, wid, uids, scale):
    """Contention-noise factors ``exp(scale * N(0,1))`` for one
    workload over a vector of config uids, each drawn from
    ``fold_in(fold_in(key_stream, wid), uid)``.

    Pure jnp — callable on host (eager) and inside jit/vmapped/sharded
    programs with bit-identical float64 results. ``key_stream`` is the
    uint32 stream key, ``wid`` a scalar workload id, ``uids`` an int
    vector of config uids.
    """
    import jax
    import jax.numpy as jnp

    key_w = jax.random.fold_in(key_stream, wid)

    def cell(uid):
        k = jax.random.fold_in(key_w, uid)
        return jnp.exp(scale * jax.random.normal(k, (), jnp.float64))

    return jax.vmap(cell)(uids)


def normal_block(key_stream, a, b, shape):
    """Standard normals of ``shape`` drawn from
    ``fold_in(fold_in(key_stream, a), b)``: the RGPE samples of a
    search with seed ``a`` at its round with ``b`` observations.

    Drawn in float32 and widened to float64, which holds them exactly:
    a sample needs no more bits, and the TPU computes float32's
    ``erf_inv`` natively where it emulates float64's, which would make
    the draw most of the replay scan's time and memory. Pure jnp like
    :func:`lognormal_noise_row`: eager on the host (the sequential
    reference) and vmapped inside the compiled replay program, with
    the same bits; call it under :func:`x64_streams`."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.fold_in(key_stream, a), b)
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.float64)


def lognormal_noise_grid(key_stream, n_workloads: int,
                         uids: np.ndarray, scale: float) -> np.ndarray:
    """The full (n_workloads, len(uids)) contention-noise grid, drawn
    on host under :func:`x64_streams` — row ``w`` is bit-identical to
    what :func:`lognormal_noise_row` yields for ``wid=w`` inside the
    compiled replay program."""
    import jax
    import jax.numpy as jnp

    with x64_streams():
        wids = jnp.arange(n_workloads)
        grid = jax.jit(jax.vmap(
            lambda w: lognormal_noise_row(key_stream, w, uids, scale)
        ))(wids)
        return np.asarray(grid, np.float64)


def bounded_uniform_grid(key_stream, n_rows: int, lo: np.ndarray,
                         hi: np.ndarray) -> np.ndarray:
    """(n_rows, len(lo)) grid of bounded uniforms: cell (r, p) is
    ``lo[p] + (hi[p] - lo[p]) * U(fold_in(fold_in(key, r), p))`` —
    row ``r`` depends only on ``r``, never on how many rows exist."""
    import jax
    import jax.numpy as jnp

    with x64_streams():
        lo = jnp.asarray(lo, jnp.float64)
        hi = jnp.asarray(hi, jnp.float64)

        def cell(r, p):
            k = jax.random.fold_in(jax.random.fold_in(key_stream, r), p)
            return lo[p] + (hi[p] - lo[p]) * jax.random.uniform(
                k, (), jnp.float64)

        grid = jax.jit(jax.vmap(jax.vmap(
            cell, in_axes=(None, 0)), in_axes=(0, None)))(
                jnp.arange(n_rows), jnp.arange(len(lo)))
        return np.asarray(grid, np.float64)


# ----------------------------------------------------------------- host
PathElem = Union[int, np.integer, str]


def _entropy(x: PathElem) -> int:
    """A path element as SeedSequence entropy: ints pass through,
    strings hash stably (blake2s, platform-independent)."""
    if isinstance(x, (int, np.integer)):
        return int(x) & ((1 << 64) - 1)
    digest = hashlib.blake2s(str(x).encode()).digest()
    return int.from_bytes(digest[:8], "little")


def folded_generator(*path: PathElem) -> np.random.Generator:
    """An independent numpy Generator keyed by a fold-in style path of
    ints/strings — e.g. ``folded_generator(seed, round, btype, mtype)``.
    Equal paths give equal streams; the draw order of *other* paths'
    generators is irrelevant."""
    return np.random.default_rng(
        np.random.SeedSequence([_entropy(x) for x in path]))


def as_generator(rng) -> np.random.Generator:
    """Accept a ``np.random.Generator`` as-is, an int seed, or a
    fold-in path tuple (via :func:`folded_generator`) — lets the
    benchmark-tool simulators take order-independent key paths without
    changing their call signature."""
    if isinstance(rng, np.random.Generator):
        return rng
    if isinstance(rng, (int, np.integer)):
        return np.random.default_rng(int(rng))
    return folded_generator(*tuple(rng))


def path_tuple(*path: PathElem) -> Tuple[PathElem, ...]:
    """Convenience constructor so call sites read as key derivations."""
    return tuple(path)
