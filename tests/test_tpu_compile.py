"""Compiles for a TPU v5e that is described, not attached: the programs
of the served path at their real sizes, refused here exactly as the
chip's compiler would refuse them (block tiling, VMEM, device memory).

Everything TPU-specific is built inside the fixtures below, so a
process that never runs these tests never loads the TPU library, and
every pytest-xdist worker collects the same tests. Keep all such
compiles in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

#: Perona's attention widths: K = 32 split over 4 heads, P = 3
#: predecessors, at a 4096-node graph.
N, HEADS, HEAD_DIM, PREDS = 4096, 4, 8, 3
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep it out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def scorer_parts():
    """A fitted preprocessor and the served model config, from a small
    seeded acquisition (their widths do not depend on its size)."""
    from repro.core.graph_data import build_graphs
    from repro.core.model import PeronaConfig, PeronaModel
    from repro.core.preprocess import Preprocessor
    from repro.fingerprint.runner import SuiteRunner
    from repro.serving.engine import prepare_inputs

    frame = SuiteRunner(seed=0).run_frame(
        {"n0": "e2-medium", "n1": "n2-standard-4"}, runs_per_type=4,
        stress_fraction=0.2)
    pre = Preprocessor().fit(frame)
    model = PeronaModel(PeronaConfig(
        feature_dim=pre.feature_dim,
        edge_dim=build_graphs(frame, pre).edge.shape[-1]))
    return model, pre, prepare_inputs(pre, frame, 64)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_edge_softmax_kernel_compiles_for_v5e(one_chip, direction):
    from repro.kernels.edge_softmax import ops

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((N, HEADS, HEAD_DIM)), sds((N, PREDS, HEADS, HEAD_DIM)),
            sds((N, PREDS, HEADS, HEAD_DIM)), sds((N, PREDS), jnp.bool_))

    def forward(q, k, v, mask):
        return ops.edge_softmax_aggregate(q, k, v, mask, interpret=False)

    def loss(q, k, v, mask):
        out, att = forward(q, k, v, mask)
        return out.sum() + (att ** 2).sum()

    fn = forward if direction == "forward" else jax.grad(
        loss, argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("requests,bucket", [(1024, 64), (16, 4096)])
def test_scorer_program_compiles_for_v5e(topo, scorer_parts, requests,
                                         bucket):
    """The fleet scoring program, through the scorer's own compile
    path, for one chip: a 1024-node round at the 64-row bucket and the
    same 65,536 rows as 16 requests of 4096."""
    from repro.fleet.shard import ShardedScorer
    from repro.serving.engine import ARG_NAMES, silence_unusable_donation

    model, pre, proto = scorer_parts
    scorer = ShardedScorer(model, pre, devices=[topo.devices[0]])
    rep = NamedSharding(scorer.mesh, PartitionSpec())
    rows = NamedSharding(scorer.mesh, PartitionSpec("fleet"))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    args = tuple(
        jax.ShapeDtypeStruct((requests, bucket) + proto[k].shape[1:],
                             proto[k].dtype, sharding=rows)
        for k in ARG_NAMES)
    with silence_unusable_donation():
        compiled = scorer._program(params, args)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES
    assert scorer.trace_count == 1


def test_sharded_f64_gp_fit_compiles_for_v5e(topo):
    """The replay's float64 GP fit, partitioned over four chips: the
    TPU compiler refuses ``jnp.linalg.cholesky`` there, so this guards
    the hand-written factorization in ``optimizer.gp``."""
    from repro.common.mesh import build_mesh, shard_map_1d
    from repro.optimizer.gp import gp_fit

    lanes, slots, dim = 8, 16, 6
    mesh = build_mesh("lanes", topo.devices)
    rows = NamedSharding(mesh, PartitionSpec("lanes"))

    def fit(x, y, mask):
        return jax.vmap(lambda *a: gp_fit(*a).l_inv)(x, y, mask)

    sharded = shard_map_1d(fit, mesh, in_specs=(PartitionSpec("lanes"),) * 3,
                           out_specs=PartitionSpec("lanes"))
    with jax.enable_x64():
        args = (jax.ShapeDtypeStruct((lanes, slots, dim), jnp.float64,
                                     sharding=rows),
                jax.ShapeDtypeStruct((lanes, slots), jnp.float64,
                                     sharding=rows),
                jax.ShapeDtypeStruct((lanes, slots), jnp.bool_,
                                     sharding=rows))
        jax.jit(sharded).lower(*args).compile()


@pytest.mark.parametrize("program", ["tables", "seeded"])
def test_replay_scan_is_loop_free_for_v5e(one_chip, program):
    """The replay's scanned BO round, at the 4,096-lane bucket of the
    benchmark's widest matrix, for one chip: the scan is the program's
    only ``while``, and no float64 contraction reaches the compiler.
    The TPU emulates a float64 dot or triangular solve as a loop of
    bfloat16 passes, so one ``@`` in ``optimizer.gp`` or
    ``optimizer.acquire`` shows here as a nested loop."""
    from repro.common.rng import x64_streams
    from repro.optimizer.replay import (ReplayConfig, _replay_fn,
                                        _seeded_replay_fn)

    cfg = ReplayConfig()
    lanes, slots, n_cand, dim, base_dim = 4096, 16, 69, 10, 6
    n_workloads, n_conds = 18, 2
    rounds = cfg.max_runs - cfg.n_init

    def sds(shape, dtype=jnp.float64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with x64_streams():
        carry = (sds((lanes, cfg.max_runs), jnp.int32),
                 sds((lanes,), jnp.int32), sds((lanes,), jnp.bool_))
        if program == "tables":
            fn = _replay_fn(cfg, lanes, slots, n_cand, dim, rounds)
            args = (carry, (
                sds((lanes, n_cand, dim)), sds((lanes, n_cand, dim)),
                sds((lanes, n_cand)), sds((lanes, n_cand)),
                sds((lanes, n_cand, 4)), sds((lanes, n_cand, 4)),
                sds((lanes, n_cand)), sds((lanes,)),
                sds((lanes,), jnp.bool_)))
        else:
            fn = _seeded_replay_fn(cfg, lanes, slots, n_cand, base_dim,
                                   rounds, n_workloads, n_conds, 0.1)
            args = (carry, (
                sds((lanes,), jnp.int32), sds((lanes,), jnp.int32),
                sds((lanes,), jnp.int32), sds((lanes,))), (
                sds((n_workloads, n_cand)), sds((n_workloads, n_cand, 4)),
                sds((4,)), sds((n_cand, base_dim)), sds((n_cand,)),
                sds((n_cand,)), sds((n_cand,), jnp.int32),
                sds((n_conds, n_cand, 4)), sds((n_conds, n_cand, 4)),
                sds((2,), jnp.uint32)))
        lowered = fn.lower(*args)
        f64_contractions = [
            line for line in lowered.as_text().splitlines()
            if re.search(r"dot_general|convolution|triangular_solve",
                         line) and "f64" in line]
        assert f64_contractions == []
        compiled = lowered.compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"\bwhile\(", hlo)) == 1
    if program == "tables":
        # the program of the scout-sec4d cells: Karasu support (M > 0)
        # adds nothing to it, op for op
        assert len(re.findall(r"\bfusion\(", hlo)) == 444
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_karasu_replay_scan_is_loop_free_for_v5e(one_chip):
    """The Karasu program (RGPE over 17 support models, 256 posterior
    samples, a 144-search support grid) at the 128-lane bucket of the
    chip smoke test's Karasu matrix, for one chip: the scan is still
    the only ``while`` (the support models' Cholesky factors are
    unrolled, the ranking losses and order statistics are reductions)
    and no float64 contraction reaches the compiler."""
    from repro.common.rng import x64_streams
    from repro.optimizer.replay import ReplayConfig, _replay_fn

    cfg = ReplayConfig()
    lanes, slots, n_cand, dim, support, n_grid = 128, 16, 69, 10, 17, 144
    rounds = cfg.max_runs - cfg.n_init

    def sds(shape, dtype=jnp.float64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with x64_streams():
        fn = _replay_fn(cfg, lanes, slots, n_cand, dim, rounds, None,
                        support)
        lowered = fn.lower(
            (sds((lanes, cfg.max_runs), jnp.int32),
             sds((lanes,), jnp.int32), sds((lanes,), jnp.bool_)),
            (sds((lanes, n_cand, dim)), sds((lanes, n_cand, dim)),
             sds((lanes, n_cand)), sds((lanes, n_cand)),
             sds((lanes, n_cand, 4)), sds((lanes, n_cand, 4)),
             sds((lanes, n_cand)), sds((lanes,)),
             sds((lanes,), jnp.bool_)),
            (sds((lanes, support), jnp.int32), sds((lanes,), jnp.uint32)),
            (sds((n_grid, n_cand)), sds((n_grid, n_cand)),
             sds((n_grid, n_cand, n_cand))))
        assert not [line for line in lowered.as_text().splitlines()
                    if re.search(r"dot_general|convolution|"
                                 r"triangular_solve", line)
                    and "f64" in line]
        compiled = lowered.compile()
    assert len(re.findall(r"\bwhile\(", compiled.as_text())) == 1
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < V5E_HBM_BYTES
